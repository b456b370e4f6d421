"""Device nanoseconds per job-lane in the scan step's ``earliest`` stage:
the kth-free kernel with its layout copies, the arrival floor and the
outage push.  The join is ``bench/stage_join.py``."""

from bench.stage_join import ns_per_job_lane


def read(run):
    return ns_per_job_lane(run, "earliest")
