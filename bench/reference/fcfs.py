"""Plain reference of the paper's FCFS scheduler, in float64.

A straightforward re-statement of the semantics that the program's
arrival-indexed core and its float64 mirror (``simulate_py``) implement,
written anew so that it shares no code with the program.  Every lane of
a campaign grid (one K and one fault draw each) runs the same stream;
the lanes are independent and are stepped side by side, job by job:

- the facility is ``S`` systems of ``n_nodes[s]`` interchangeable nodes,
  each with a free-from time (all 0 at the start); a lane keeps each
  system's free times sorted, padded with +inf up to the largest system;
- jobs are placed one by one in arrival order.  Job ``j`` of program
  ``p`` picks a system by the paper's rule on the learned tables
  (warm start: the tables begin at the truth, with one run each):
  among the systems whose learned runtime is within ``(1 + K)`` of the
  fastest, the least learned energy coefficient, ties to the shorter
  runtime, then the lower index;
- it starts on system ``s`` when ``n_req[p, s]`` nodes are free, and
  not before its arrival: ``max(arrival, n_req-th smallest free time)``;
  it takes the earliest-free nodes until ``start + T``;
- a straggler runs ``factor`` times longer and draws ``factor`` times
  the energy; the learned tables absorb the observed (scaled) values as
  running means.

``rnd`` rounds every stored number: the identity for the reference,
a cast to bfloat16 for the lower-precision control.
"""

from __future__ import annotations

import numpy as np

from bench.reference.common import facility, same


def paper_rule(c, t, k) -> np.ndarray:
    """Per lane: argmin C over {s : T[s] <= min T * (1 + K)}, ties to the
    smaller T, then the lower index.  ``c``, ``t``: [L, S]; ``k``: [L]."""
    ok = t <= (t.min(axis=1) * (1.0 + k))[:, None]
    c_ok = np.where(ok, c, np.inf)
    best_c = c_ok == c_ok.min(axis=1)[:, None]
    t_ok = np.where(best_c, t, np.inf)
    return np.argmax(best_c & (t_ok == t_ok.min(axis=1)[:, None]), axis=1)


def fcfs(tables: dict, prog, arrival, k, factor, *, rnd=same) -> dict:
    """Schedule the stream ``(prog, arrival)`` in every lane: ``k`` [L]
    is each lane's K, ``factor`` [L, J] its per-job fault factor.
    Returns each lane's totals and learned tables, lane first."""
    f = facility(tables)
    n_nodes = f["n_nodes"]
    n_req = np.asarray(f["n_req"], np.int64)
    T, C, E = (np.asarray(f[x], np.float64) for x in ("T", "C", "E"))
    k = np.asarray(k, np.float64)
    factor = np.asarray(factor, np.float64)
    L, (P, S) = k.size, T.shape
    lane = np.arange(L)
    slot = np.arange(max(n_nodes))
    free = np.full((L * S, slot.size), np.inf)     # row l * S + s
    for s, n in enumerate(n_nodes):
        free[s::S, :n] = 0.0
    C_tab = np.repeat(rnd(C)[None], L, axis=0)
    T_tab = np.repeat(rnd(T)[None], L, axis=0)
    runs = np.ones(C_tab.shape, np.int64)
    C_flat, T_flat, runs_flat = (a.reshape(-1) for a in (C_tab, T_tab,
                                                         runs))
    e_sum, w_sum, sd_sum, fin_max, w_max = (np.zeros(L) for _ in range(5))
    busy = np.zeros(L * S)
    for j in range(len(prog)):
        p = int(prog[j])
        arr = float(arrival[j])
        s = paper_rule(C_tab[:, p], T_tab[:, p], k)
        at = lane * (P * S) + p * S + s            # (lane, p, s), flat
        r = lane * S + s                           # (lane, s), flat
        need = n_req[p, s]
        row = free[r]
        start = rnd(np.maximum(arr, row[lane, need - 1]))
        fac = factor[:, j]
        t_act = rnd(T[p, s] * fac)
        finish = rnd(start + t_act)
        row = np.where(slot < need[:, None], finish[:, None], row)
        row.sort(axis=1)
        free[r] = row
        n = runs_flat[at]
        C_flat[at] = rnd((C_flat[at] * n + C[p, s] * fac) / (n + 1))
        T_flat[at] = rnd((T_flat[at] * n + t_act) / (n + 1))
        runs_flat[at] = n + 1
        wait = rnd(start - arr)
        e_sum = rnd(e_sum + rnd(E[p, s] * fac))
        w_sum = rnd(w_sum + wait)
        sd_sum = rnd(sd_sum + rnd((wait + t_act) / t_act))
        fin_max = np.maximum(fin_max, finish)
        w_max = np.maximum(w_max, wait)
        busy[r] = rnd(busy[r] + t_act * need)
    return {"total_energy": e_sum, "makespan": fin_max, "total_wait": w_sum,
            "slowdown_sum": sd_sum, "max_wait": w_max,
            "busy": busy.reshape(L, S), "C_tab": C_tab, "T_tab": T_tab,
            "runs": runs}
