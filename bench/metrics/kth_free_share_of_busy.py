"""Share of the device's busy time spent in the Pallas kth-free placement
kernel, in percent; nothing where the trace holds no such kernel."""


def read(run):
    t = run["trace"]
    if t is None or t.busy_total_s <= 0:
        return None
    n, secs = t.kernel("kth_free")
    if not n:
        return None
    return 100.0 * secs / t.busy_total_s
