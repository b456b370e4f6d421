"""Device busy nanoseconds per simulated job-lane in the traced stretch,
summed over the devices that ran it.  The work is counted in the trace
itself: the arrival core places one job per scan step in every lane of
its device, with one kth-free kernel call, so the job-lanes covered are
the kernel's events times the lanes per device.  Nothing where the
trace holds no such kernel."""


def read(run):
    t, c = run["trace"], run["counters"]
    if t is None or "lanes_per_device" not in c or t.busy_total_s <= 0:
        return None
    steps, _ = t.kernel("kth_free")
    if not steps:
        return None
    return t.busy_total_s * 1e9 / (steps * c["lanes_per_device"])
