"""Device nanoseconds per job-lane in the scan step's ``fault`` stage: the
fault draw and the realised runtime and energy it scales.  The join is
``bench/stage_join.py``."""

from bench.stage_join import ns_per_job_lane


def read(run):
    return ns_per_job_lane(run, "fault")
