"""The join behind the ``step_ns_per_job_lane.<stage>`` metrics: device
time of the traced stretch by stage of the scan step.

The program names each stage of its step with ``jax.named_scope``
(``repro.obs``), and ``repro.obs.stage_table()`` maps each compiled
instruction of the programs the last ``Scheduler.run`` dispatched to its
stage.  The trace names operations by instruction, so a stage's time is
the summed time of the operations the table gives it.  The work is
counted as ``scan_device_ns_per_job_lane`` counts it: kth-free kernel
events (one per arrival-core step) times the lanes per device, so the
seven stages add up to that metric.

Nothing where the program has no stage table (it predates
``repro.obs``), where the trace holds no kth-free kernel, or where more
than ``MAX_UNKNOWN`` of the busy time lies in operations the table does
not know: a wrong split is worse than none.
"""

from __future__ import annotations

import sys
import time

from bench.trace_reduce import CUSTOM

#: share of busy time that may lie in operations the table does not know
MAX_UNKNOWN = 0.01

_table = []          # the stage table, once built (None: the program has none)


def stage_table() -> dict | None:
    """``repro.obs.stage_table()``, built once per process; None where the
    program has no such table or recorded no run."""
    if not _table:
        try:
            from repro import obs
        except ImportError:
            _table.append(None)
            return None
        t0 = time.perf_counter()
        try:
            table = obs.stage_table()
        except LookupError:
            table = None
        _table.append(table)
        if table is not None:
            print(f"stage table: {len(table)} instructions in "
                  f"{time.perf_counter() - t0:.3f} s", file=sys.stderr,
                  flush=True)
    return _table[0]


def seconds_by_stage(reduced, table: dict) -> dict:
    """Summed seconds of the reduced trace's operations per stage; those
    the table does not know are under None."""
    out = {}
    for name, (_, secs) in reduced.ops.items():
        if name.endswith(CUSTOM):
            name = name[:-len(CUSTOM)]
        st = table.get(name.lstrip("%"))
        out[st] = out.get(st, 0.0) + secs
    return out


def ns_per_job_lane(run, stage: str, table: dict | None = None):
    """Device nanoseconds of ``stage`` per job-lane of the traced stretch
    (``table``: the stage table, else the program's)."""
    t, c = run["trace"], run["counters"]
    if t is None or "lanes_per_device" not in c or t.busy_total_s <= 0:
        return None
    steps, _ = t.kernel("kth_free")
    if not steps:
        return None
    table = stage_table() if table is None else table
    if table is None:
        return None
    secs = seconds_by_stage(t, table)
    if secs.get(None, 0.0) > MAX_UNKNOWN * t.busy_total_s:
        return None
    return secs.get(stage, 0.0) * 1e9 / (steps * c["lanes_per_device"])
