"""What-if queries: fork the live carry into a jitted rollout.

An operator deciding whether (or when) to submit a job wants the
projected consequences — wait, system, cap headroom — WITHOUT committing
the submission.  ``whatif`` copies the dispatcher's context, writes the
hypothetical job into the next free slot of the (functionally-updated)
job arrays, and folds the SAME factored step the live session runs
through a fixed-length ``lax.scan`` from the CURRENT carry.  Everything
is functional: the live carry and job arrays are never written
(tests/test_service.py pins snapshot equality), and the projection is
exactly what the session would realize if the job were submitted now and
no other job arrived after it.

The rollout is jitted once per dispatcher (fixed scan length from the
session capacity), so repeated queries cost microseconds, not a
recompile.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.engine import BIG, UNCAPPED, _event_results, event_context


def _rollout_fn(disp):
    """Build (once) and cache the dispatcher's jitted what-if rollout."""
    fn = getattr(disp, "_whatif_rollout", None)
    if fn is None:
        step = disp._step_fn
        mult = (9 if disp._retries else 5) \
            if disp.policy.queue == "conservative" \
            else (7 if disp._retries else 4)
        T = mult * disp.capacity + disp._n_out + 4
        hor = jnp.float32(BIG)

        @jax.jit
        def fn(ctx, carry):
            return jax.lax.scan(lambda c, _: step(ctx, c, hor), carry,
                                None, length=T)
        disp._whatif_rollout = fn
    return fn


def whatif(disp, prog: int, arrival: float | None = None,
           k: float | None = None, *, t_scale: float | None = None,
           nodes=None) -> dict:
    """Project submitting ``prog`` at ``arrival`` (default: now) into the
    live session, without mutating it.  Returns the hypothetical job's
    projected placement (system, start, wait, finish), the session-level
    projections (mean wait over all submitted + hypothetical jobs,
    makespan, peak power), and the cap headroom at the projected peak
    (``inf`` when uncapped).  ``t_scale``/``nodes``: the job's own
    runtime scale and node counts, as ``Dispatcher.submit`` takes them."""
    if disp.n_submitted >= disp.capacity:
        raise RuntimeError("session full: no free slot for a what-if job")
    if not 0 <= int(prog) < disp.w.T_true.shape[0]:
        raise ValueError(f"prog {prog} not in the facility catalog")
    t = float(disp.now if arrival is None else arrival)
    if t < disp.now:
        raise ValueError(f"arrival {t} is in the past (now={disp.now})")

    j = disp.n_submitted
    arrs = dict(disp._arrs)
    for key, v in disp.job_row(prog, t, k, t_scale, nodes).items():
        arrs[key] = arrs[key].at[j].set(v)
    ctx = event_context(arrs, disp.policy, disp.seed, disp._fvec)

    carry_f, ys = _rollout_fn(disp)(ctx, disp._carry)
    proj = _event_results(arrs, False, ys, carry_f)
    proj = jax.device_get(proj)

    # decided channels of already-finished jobs are zeros in the rollout's
    # scatter (their steps pre-date the fork) — splice the realized values
    n = j + 1
    wait = np.asarray(disp._wait[:n], np.float32).copy()
    fin = np.asarray(disp._fin[:n], np.float32).copy()
    live_done = fin > 0
    wait[~live_done] = proj["wait"][:n][~live_done]
    fin[~live_done] = proj["finish"][:n][~live_done]

    cap = float(np.asarray(disp.policy.power_cap).reshape(-1)[0])
    peak = float(proj["peak_power"])
    return {
        "job": {"prog": int(prog), "arrival": t,
                "system": int(proj["system"][j]),
                "start": float(proj["start"][j]),
                "wait": float(proj["wait"][j]),
                "finish": float(proj["finish"][j]),
                "backfilled": bool(proj["backfilled"][j])},
        "mean_wait": float(wait.mean()) if n else 0.0,
        "makespan": float(fin.max()) if n else 0.0,
        "peak_power": peak,
        "cap_headroom": float("inf") if cap >= UNCAPPED else cap - peak,
    }
