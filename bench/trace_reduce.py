"""From a profiler trace (``.xplane.pb``) to busy time, kernel time and
idle gaps.

- Device planes are the ``/device:TPU:<n>`` planes.  Their operations
  are the events of the ``XLA Ops`` line.  An operation that encloses
  others (a ``while`` around its body's operations) is a container and
  is left out, so each nanosecond is counted once, by the operation
  that ran in it, and the gaps between a loop body's operations show.
- The window is the host span named ``span`` (the harness's
  ``bench.window``), on the trace's own clock; operations are cut to it.
  Without a span name, the window is the extent of the operations.
- Busy time of a device is the union of its leaf operations'
  intervals; ``busy_s`` is its mean over the devices traced.  It can
  never exceed the window: where it does, the reduction is wrong, and it
  raises.
- An idle gap is a stretch between two busy intervals of a device; it is
  labelled by the benchmark's host span (``campaign.*``) that overlaps
  it most, or ``no span`` where none does.

Reading needs nothing but ``jax.profiler.ProfileData``, so a recorded
trace can be reduced without a chip.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIXES = ("campaign.", "bench.")
CUSTOM = " (custom-call)"


def short_name(hlo: str) -> str:
    """An operation's instruction name (``%fusion.12``), marked when it
    is a custom call (a Pallas kernel): the trace names operations by
    their whole HLO text."""
    name = hlo.split(" = ", 1)[0]
    return name + CUSTOM if "custom-call(" in hlo else name


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


@dataclass
class Reduced:
    window_s: float
    busy_s: float                          # mean over devices
    devices: int
    ops: dict = field(default_factory=dict)    # name -> [count, seconds]
    gaps: list = field(default_factory=list)   # (seconds, label)
    busy_total_s: float = 0.0              # summed over devices

    def kernel(self, needle: str) -> tuple[int, float]:
        """(events, seconds summed over devices) of the custom-call
        kernels whose instruction name holds ``needle``."""
        n = s = 0
        for name, (c, t) in self.ops.items():
            if needle in name and name.endswith(CUSTOM):
                n += c
                s += t
        return n, s

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:10]
        return {"device_ops": [[n, t] for n, (_, t) in top],
                "idle_gaps": [[lab, s] for s, lab in gaps]}


def leaves(events):
    """``(start, end, name)`` of the events that enclose no other event
    of their line."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (s, e, name) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][0] < e:
            continue                       # the next one starts inside
        out.append((s, e, name))
    return out


def reduce(path, span: str | None = None) -> Reduced | None:
    """Reduce the trace at ``path`` over the host span ``span`` (None:
    over the extent of the device operations).  None where the trace
    holds no TPU plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append([(ev.start_ns, ev.start_ns + ev.duration_ns,
                             short_name(ev.name))
                            for ln in plane.lines if ln.name == "XLA Ops"
                            for ev in ln.events])
        elif plane.name.startswith("/host:"):
            spans += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ln in plane.lines for ev in ln.events
                      if ev.name.startswith(SPAN_PREFIXES)]
    return reduce_events(devices, spans, span)


def reduce_events(devices, spans, span: str | None = None) -> Reduced | None:
    """``devices``: per device, its ``(start_ns, end_ns, name)``
    operations; ``spans``: the host spans, likewise."""
    if not devices:
        return None
    per_dev = [leaves(evs) for evs in devices]
    if span is None:
        lo = min((e[0] for evs in per_dev for e in evs), default=0)
        hi = max((e[1] for evs in per_dev for e in evs), default=0)
    else:
        found = [(a, b) for a, b, name in spans if name == span]
        if len(found) != 1:
            raise ValueError(f"the trace holds {len(found)} {span!r} spans, "
                             f"not one")
        lo, hi = found[0]
    window_s = (hi - lo) * 1e-9
    ops, unions = {}, []
    for evs in per_dev:
        iv = []
        for s, e, name in evs:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            iv.append((s, e))
            c = ops.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (e - s) * 1e-9
        unions.append(merge(iv))
    busy = [sum(e - s for s, e in m) * 1e-9 for m in unions]
    if max(busy) > window_s:
        raise ValueError(f"device busy {max(busy)} s exceeds the traced "
                         f"window {window_s} s")
    labels = [x for x in spans if x[2] != span]
    gaps = [((s1 - e0) * 1e-9, _label(e0, s1, labels))
            for (_, e0), (s1, _) in zip(unions[0], unions[0][1:])]
    return Reduced(window_s=window_s, busy_s=sum(busy) / len(unions),
                   devices=len(unions), ops=ops, gaps=gaps,
                   busy_total_s=sum(busy))


def _label(s, e, spans) -> str:
    best, lab = 0, "no span"
    for a, b, name in spans:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, lab = ov, name
    return lab
