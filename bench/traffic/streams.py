"""Job streams for the benchmark, built from the seed alone.

A vectorised copy of the program's NPB stream generators
(``repro.data.scenarios``: ``diurnal_arrivals``, ``sample_programs``),
kept here so that a later change to the program cannot move the
yardstick.  The numbers differ from the program's (those loop in Python
and draw in another order); the processes are the same:

- arrivals: an inhomogeneous Poisson process whose rate follows
  ``rate * (base + (peak - base) / 2 * (1 + sin(2 pi t / period)))``,
  sampled by thinning against the peak rate, so its mean is ``rate``;
- programs: a size class drawn by weight, then a program of the class
  uniformly (the paper's Table 6 classes: BT/EP on few nodes, IS/LU/SP
  on many).
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """An independent generator per (run seed, stream index)."""
    return np.random.default_rng([int(seed) % 2**63, int(stream)])


def diurnal_arrivals(rng, n: int, rate: float, base: float, peak: float,
                     period: float) -> np.ndarray:
    """``n`` sorted submit times (float32 seconds) of the diurnal process."""
    lo, hi = rate * base, rate * peak
    out, t0 = [], 0.0
    need = n
    while need > 0:
        m = int(need * hi / rate * 1.25) + 64
        t = t0 + np.cumsum(rng.exponential(1.0 / hi, m))
        lam = lo + 0.5 * (hi - lo) * (1.0 + np.sin(2.0 * np.pi * t / period))
        keep = t[rng.uniform(size=m) * hi <= lam][:need]
        out.append(keep)
        need -= keep.size
        t0 = float(t[-1])
    return np.concatenate(out).astype(np.float32)


def programs(rng, n: int, classes, weights, catalog) -> np.ndarray:
    """``n`` program indices into ``catalog``: class by weight, then a
    member of the class uniformly."""
    w = np.asarray(weights, np.float64)
    cls = rng.choice(len(classes), size=n, p=w / w.sum())
    pick = rng.uniform(size=n)
    out = np.empty(n, np.int32)
    for c, members in enumerate(classes):
        ids = np.asarray([catalog.index(m) for m in members], np.int32)
        at = cls == c
        out[at] = ids[np.minimum((pick[at] * len(ids)).astype(np.int64),
                                 len(ids) - 1)]
    return out


def npb_stream(rng, n: int, traffic: dict, catalog):
    """(prog [n] int32, arrival [n] float32) of one NPB diurnal stream."""
    a = traffic["arrivals"]
    arrival = diurnal_arrivals(rng, n, a["rate_per_s"], a["base"],
                               a["peak"], a["period_s"])
    prog = programs(rng, n, traffic["mix"]["classes"],
                    traffic["mix"]["weights"], catalog)
    return prog, arrival
