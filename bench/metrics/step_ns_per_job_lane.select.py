"""Device nanoseconds per job-lane in the scan step's ``select`` stage: the
selection key and the policy's choice of system.  The join is
``bench/stage_join.py``."""

from bench.stage_join import ns_per_job_lane


def read(run):
    return ns_per_job_lane(run, "select")
