"""Device nanoseconds per job-lane in the scan step's ``loop`` stage: the
scan's own work outside every stage (xs slicing, the trip counter, the
condition, carry copies).  The join is ``bench/stage_join.py``."""

from bench.stage_join import ns_per_job_lane


def read(run):
    return ns_per_job_lane(run, "loop")
