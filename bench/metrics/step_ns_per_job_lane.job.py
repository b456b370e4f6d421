"""Device nanoseconds per job-lane in the scan step's ``job`` stage: the
reads of the workload's per-job columns (runtime scale, node counts) and
the products that turn a class's runtime, energy and draw into the
job's own.  The join is ``bench/stage_join.py``."""

from bench.stage_join import ns_per_job_lane


def read(run):
    return ns_per_job_lane(run, "job")
