"""Tiny sizes of the traffic kinds added after ``bench/tests/tiny.py``'s
table, registered before any test of the harness runs, so that the tests
parametrised over every cell of ``BENCHMARK.json`` run these cells too."""

from bench.tests import tiny

#: the rigid-job campaign cut to a size the CPU runs in seconds (its node
#: rows stay the facility's)
tiny.TINY.setdefault("campaign-rigid", {"jobs": 400})
