"""The kth-free kernel's share of its roofline, in percent.

The work is reckoned from shapes, whatever implements the kernel: one
event covers the lanes of one device, and for each lane reads the
[S, maxN] f32 node-free table and the [S] i32 node counts and writes
the [S] f32 kth free times.  It does no floating-point work worth a
bound, so the memory bound sets the least time: bytes over the chip's
HBM bandwidth.  The share is that least time over the events' summed
duration; nothing where the trace holds no such kernel."""


def bytes_per_event(lanes: int, systems: int, max_nodes: int) -> int:
    return lanes * (systems * max_nodes * 4 + systems * 4 + systems * 4)


def read(run):
    t, c = run["trace"], run["counters"]
    if t is None or "lanes_per_device" not in c:
        return None
    n, secs = t.kernel("kth_free")
    if not n or secs <= 0:
        return None
    b = bytes_per_event(c["lanes_per_device"], c["systems"], c["max_nodes"])
    least = n * b / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
