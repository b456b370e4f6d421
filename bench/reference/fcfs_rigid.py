"""Plain reference of the paper's FCFS scheduler over rigid jobs, each at
its own runtime and width, in float64.

The semantics of ``fcfs.py`` with per-job columns, written anew so that
it shares no code with the program:

- job ``j`` of class ``p`` picks a system by the paper's rule on the
  learned per-class tables (warm start: the tables begin at the class
  truth, with one run each);
- on system ``s`` it needs ``n_job[j, s]`` nodes, starts at
  ``max(arrival, n_job-th smallest free time)``, and runs
  ``T[p, s] * fault * t_scale[j]`` seconds on the earliest-free nodes;
- it draws ``E[p, s] * fault * t_scale[j] * n_job[j, s] / n_req[p, s]``
  joules (its class's energy, scaled by its runtime and its width); its
  class's energy coefficient ``C[p, s] * fault`` and its runtime are what
  the learned tables absorb, as running means per class.

Each (lane, system) keeps its nodes' free times as one sorted row of the
system's own length (nodes are interchangeable, so the sorted multiset is
the whole state).  A placement drops the row's first ``need`` entries and
puts ``need`` copies of the finish where they sort (``np.searchsorted``):
O(nodes) a job, where re-sorting a 9,688-wide row a job would take
minutes.

``rnd`` rounds every stored number: the identity for the reference, a
cast to bfloat16 for the lower-precision control.
"""

from __future__ import annotations

import numpy as np

from bench.reference.common import same
from bench.reference.fcfs import paper_rule


def fcfs_rigid(tables: dict, prog, arrival, t_scale, n_job, k, factor, *,
               rnd=same) -> dict:
    """Schedule the stream in every lane: ``k`` [L] each lane's K,
    ``factor`` [L, J] its per-job fault factor; ``t_scale`` [J] and
    ``n_job`` [J, S] the jobs' own runtime scales and node counts.
    Returns each lane's totals and learned tables, lane first."""
    T = np.asarray(tables["T_true"], np.float64)
    C = np.asarray(tables["C_true"], np.float64)
    E = np.asarray(tables["E_true"], np.float64)
    n_req = np.asarray(tables["n_req"], np.float64)
    n_nodes = [int(n) for n in tables["n_nodes"]]
    prog = np.asarray(prog, np.int64)
    arrival = np.asarray(arrival, np.float64)
    t_scale = np.asarray(t_scale, np.float64)
    n_job = np.asarray(n_job, np.int64)
    k = np.asarray(k, np.float64)
    factor = np.asarray(factor, np.float64)
    L, (P, S) = k.size, T.shape
    lane = np.arange(L)
    free = [[np.zeros(n) for n in n_nodes] for _ in range(L)]
    C_tab = np.repeat(rnd(C)[None], L, axis=0)
    T_tab = np.repeat(rnd(T)[None], L, axis=0)
    runs = np.ones(C_tab.shape, np.int64)
    e_sum, w_sum, sd_sum, fin_max, w_max = (np.zeros(L) for _ in range(5))
    busy = np.zeros((L, S))
    for j in range(prog.size):
        p = prog[j]
        s = paper_rule(C_tab[:, p], T_tab[:, p], k)
        need = n_job[j, s]
        fac = factor[:, j]
        t_act = rnd(T[p, s] * fac * t_scale[j])
        start = np.empty(L)
        for ln in range(L):
            row = free[ln][s[ln]]
            n = need[ln]
            start[ln] = rnd(max(arrival[j], row[n - 1]))
            fin = rnd(start[ln] + t_act[ln])
            at = int(np.searchsorted(row[n:], fin, side="right"))
            row[:at] = row[n:n + at]
            row[at:at + n] = fin
        finish = rnd(start + t_act)
        n_run = runs[lane, p, s]
        C_tab[lane, p, s] = rnd((C_tab[lane, p, s] * n_run + C[p, s] * fac)
                                / (n_run + 1))
        T_tab[lane, p, s] = rnd((T_tab[lane, p, s] * n_run + t_act)
                                / (n_run + 1))
        runs[lane, p, s] = n_run + 1
        wait = rnd(start - arrival[j])
        e_job = rnd(E[p, s] * fac * t_scale[j] * (need / n_req[p, s]))
        e_sum = rnd(e_sum + e_job)
        w_sum = rnd(w_sum + wait)
        sd_sum = rnd(sd_sum + rnd((wait + t_act) / t_act))
        fin_max = np.maximum(fin_max, finish)
        w_max = np.maximum(w_max, wait)
        busy[lane, s] = rnd(busy[lane, s] + t_act * need)
    return {"total_energy": e_sum, "makespan": fin_max, "total_wait": w_sum,
            "slowdown_sum": sd_sum, "max_wait": w_max, "busy": busy,
            "C_tab": C_tab, "T_tab": T_tab, "runs": runs}
