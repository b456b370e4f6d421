"""Device nanoseconds per job-lane in the scan step's ``alloc`` stage: the
node-free table update of the placement (``_alloc`` with its tie-break
cumsum).  The join is ``bench/stage_join.py``."""

from bench.stage_join import ns_per_job_lane


def read(run):
    return ns_per_job_lane(run, "alloc")
