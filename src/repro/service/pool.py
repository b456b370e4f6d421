"""Multi-session scale-out: N facility sessions, ONE vmapped step.

``SessionPool`` holds N concurrent ``Dispatcher`` sessions as one
stacked carry pytree and advances all of them with a single jitted
``jax.vmap`` of the factored event step — one compile serves the whole
pool because sessions may differ only in policy LEAVES (K, power cap,
frequency weight, per-session seed streams), never in static
composition (queue discipline, window, tier grid, placer, retry mode).
The jit cache is asserted after every drive: a retrace means a session
broke that contract.

Intake is BATCHED: ``submit`` buffers per session and the buffer is
flushed in one scatter into the stacked job arrays when that session is
next driven (``drive``/``drain``) or read (``result``/``whatif``/
``save``).  Lanes that are not being driven hold their last horizon and
their job arrays untouched, so their steps are carry no-ops — each
session's decision sequence stays bit-identical to an independent
``Dispatcher`` fed the same stream (tests/test_service_pool.py).

Decision records and non-blocking checkpoints flow through one
``AsyncWriter`` thread (bounded queue, drain-on-close), so intake never
blocks on disk.  Checkpoints are namespaced per session (``s000``,
``s001``, ...) under one ``checkpoint_dir`` root; ``restore`` brings
any or all sessions back bit-identically.  See docs/SERVICE.md.
"""

from __future__ import annotations

import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.engine import (BIG, Scheduler, Workload, event_context,
                               index_session, stack_sessions)
from repro.service.dispatcher import Dispatcher
from repro.service.whatif import whatif as _whatif
from repro.service.writer import AsyncWriter


class SessionPool:
    """N live scheduling sessions advanced by one jitted vmapped step.

    ``scheds`` is one batch ``Scheduler`` per session (the same unified
    spec ``Dispatcher.from_scheduler`` adopts); all must share one
    static composition — same queue discipline, window, placer, tier
    grid, fault/retry mode and capacity — while leaves (K, power cap,
    freq weight) and seeds may differ per session.  ``decision_log``
    arms an append-only JSONL sink (``{"session": i, ...decision}`` per
    line) written by the async writer thread.
    """

    def __init__(self, scheds, w: Workload, *, capacity: int | None = None,
                 checkpoint_dir: str | None = None, keep_n: int = 3,
                 decision_log: str | None = None, writer_queue: int = 256):
        scheds = list(scheds)
        if not scheds:
            raise ValueError("a pool needs at least one session")
        self.w = w
        self.sessions = [
            Dispatcher.from_scheduler(
                s, w, capacity=capacity, checkpoint_dir=checkpoint_dir,
                keep_n=keep_n, checkpoint_namespace=f"s{i:03d}")
            for i, s in enumerate(scheds)]
        self.n = len(self.sessions)

        d0 = self.sessions[0]
        ref = jax.tree.structure(d0.policy)
        for i, d in enumerate(self.sessions[1:], start=1):
            if jax.tree.structure(d.policy) != ref:
                raise ValueError(
                    f"session {i} breaks the pool's static composition: "
                    f"policy metadata (queue/window/tiers/...) must match "
                    f"session 0 — only leaves (k, power_cap, freq_weight, "
                    f"ucb_scale) may differ")
            if (d.placer != d0.placer or d._retries != d0._retries
                    or d.capacity != d0.capacity
                    or d.warm_start != d0.warm_start):
                raise ValueError(
                    f"session {i} differs from session 0 in placer/retry/"
                    f"capacity/warm-start — those are static, one compile "
                    f"covers one composition")
        self.capacity = d0.capacity
        self._n_out = d0._n_out

        # ONE step for the whole pool: vmap over (policy leaves, ctx,
        # carry, horizon); the builder re-runs under trace with the
        # leaf-batched policy, metadata stays static -> one compile.
        build, placer, retries = d0._build_step, d0.placer, d0._retries

        def _lane(pol, ctx, carry, hor):
            return build(pol, placer, totals_only=False,
                         retries=retries)(ctx, carry, hor)

        self._step = jax.jit(jax.vmap(_lane, in_axes=(0, 0, 0, 0)))

        self._restack()
        self._horizons = np.zeros(self.n, np.float32)
        self._buffers: list[list] = [[] for _ in range(self.n)]
        self.n_pool_steps = 0
        self.wall_us_total = 0.0
        self.wall_us_max = 0.0
        self._writer = AsyncWriter(maxsize=writer_queue)
        self._log_f = open(decision_log, "a") if decision_log else None

    @classmethod
    def replicate(cls, sched: Scheduler, n: int, w: Workload,
                  **kw) -> "SessionPool":
        """N sessions of one configuration (the ``--pool N`` CLI path)."""
        return cls([sched] * int(n), w, **kw)

    # ----------------------------------------------------- stacked state
    def _restack(self):
        """Rebuild the pool's stacked pytrees from the member sessions
        (construction and restore; members are authoritative there)."""
        ds = self.sessions
        self._pol = stack_sessions([d.policy for d in ds])
        self._ctx = stack_sessions([d._ctx for d in ds])
        self._carry = stack_sessions([d._carry for d in ds])

    def _flush(self, idxs) -> int:
        """Scatter the buffered submissions of the given sessions into
        the stacked job arrays — ONE scatter per channel regardless of
        how many jobs or sessions flush — then sync those members.
        Un-flushed lanes' arrays are untouched, so their steps stay
        no-ops."""
        si, ji, rows, touched = [], [], [], []
        for i in idxs:
            buf = self._buffers[i]
            if not buf:
                continue
            touched.append(i)
            base = self.sessions[i].n_submitted
            for off, row in enumerate(buf):
                si.append(i)
                ji.append(base + off)
                rows.append(row)
        if not touched:
            return 0
        si = np.asarray(si, np.int32)
        ji = np.asarray(ji, np.int32)
        arrs = self._ctx["arrs"]
        new = {key: arrs[key].at[si, ji].set(
                   np.asarray([r[key] for r in rows], arrs[key].dtype))
               for key in self.sessions[0].job_keys}
        k_job = new["k_job"]
        # the stacked twin of event_context's kvec (same elementwise
        # where, so each lane matches its member's own rebuild bitwise)
        kvec = jnp.where(jnp.isnan(k_job),
                         jnp.asarray(self._pol.k, jnp.float32)[:, None],
                         k_job)
        self._ctx = {**self._ctx, "kvec": kvec, "arrs": {**arrs, **new}}
        for i in touched:
            d = self.sessions[i]
            for key, v in new.items():
                d._arrs[key] = v[i]
            d._ctx = event_context(d._arrs, d.policy, d.seed, d._fvec)
            d.n_submitted += len(self._buffers[i])
            self._buffers[i].clear()
        return len(si)

    # ------------------------------------------------------------ intake
    def submit(self, session: int, prog: int, arrival: float | None = None,
               k: float | None = None, *, t_scale: float | None = None,
               nodes=None) -> int:
        """Buffer one submission for ``session`` (validated now, flushed
        in one scatter at that session's next drive/read; ``t_scale`` and
        ``nodes`` as ``Dispatcher.submit``).  Returns the job id —
        assigned immediately, intake never waits on the pool."""
        i = int(session)
        d = self.sessions[i]
        buf = self._buffers[i]
        t = float(d.now if arrival is None else arrival)
        last = float(buf[-1]["arrival"]) if buf else None
        d._validate_intake(prog, t, queued=len(buf), last=last)
        row = d.job_row(prog, t, k, t_scale, nodes)
        j = d.n_submitted + len(buf)
        buf.append(row)
        d.metrics.observe_submit()
        return j

    # ------------------------------------------------------------- drive
    def _run(self):
        """Step the whole pool until globally quiescent under the
        per-session horizon vector, folding each lane's decision channels
        into its member session."""
        hor = jnp.asarray(self._horizons)
        limit = 16 * self.capacity + self._n_out + 64
        ds = self.sessions
        for _ in range(limit):
            t0 = time.perf_counter()
            carry, out = self._step(self._pol, self._ctx, self._carry, hor)
            out = jax.device_get(out)
            dt_us = (time.perf_counter() - t0) * 1e6
            self._carry = carry
            self.n_pool_steps += 1
            self.wall_us_total += dt_us
            self.wall_us_max = max(self.wall_us_max, dt_us)
            share = dt_us / self.n       # amortized per-session step cost
            progress = False
            for i, d in enumerate(ds):
                oi = {key: val[i] for key, val in out.items()}
                d._record(oi)
                d.metrics.observe_step(oi, share)
                progress = (progress or bool(oi["pushed"])
                            or bool(oi["placed"]) or bool(oi["advanced"]))
            if not progress:
                break
        else:
            raise RuntimeError("pool drive exceeded its step budget — a "
                               "lane's carry is diverging (engine bug)")
        for i, d in enumerate(ds):
            d._carry = index_session(self._carry, i)
        size = getattr(self._step, "_cache_size", lambda: 1)()
        if size > 1:
            raise RuntimeError(
                f"pool step retraced ({size} compiles): sessions were "
                f"promised to share one static composition")

    def drive(self, until: float = BIG, session: int | None = None):
        """Advance sessions to ``until``: all of them (returns
        ``{session: [decisions]}``) or one (returns its decisions).
        Other lanes hold their last horizon — no-op steps, no state
        drift."""
        idxs = list(range(self.n)) if session is None else [int(session)]
        self._flush(idxs)
        for i in idxs:
            self._horizons[i] = np.float32(until)
        n0 = [len(d.decisions) for d in self.sessions]
        self._run()
        new = {i: list(self.sessions[i].decisions[n0[i]:])
               for i in range(self.n)}
        self._log_decisions(new)
        return new[int(session)] if session is not None else new

    def drain(self, session: int | None = None):
        """Run sessions to completion (open horizon)."""
        return self.drive(BIG, session)

    def _log_decisions(self, new: dict):
        if self._log_f is None:
            return
        for i in sorted(new):
            for dec in new[i]:
                line = json.dumps({"session": i, **dec}) + "\n"
                self._writer.submit(self._log_f.write, line)

    # ----------------------------------------------------------- queries
    def now(self, session: int) -> float:
        return self.sessions[int(session)].now

    def metrics(self, session: int) -> dict:
        return self.sessions[int(session)].metrics.snapshot()

    def result(self, session: int):
        """The realized ``SimResult`` of one session (buffer flushed
        first — a submitted job is part of the session even before its
        lane is driven)."""
        i = int(session)
        self._flush([i])
        return self.sessions[i].result()

    def whatif(self, session: int, prog: int, arrival: float | None = None,
               k: float | None = None, **job) -> dict:
        """Project a hypothetical submission into one session — served
        from that member's cached jitted fork, the pool never stalls
        (``job``: ``t_scale``/``nodes`` as ``Dispatcher.submit``)."""
        i = int(session)
        self._flush([i])
        return _whatif(self.sessions[i], prog, arrival, k, **job)

    @property
    def mean_step_us(self) -> float:
        """Mean wall-clock of one pool step (all N lanes advance)."""
        return self.wall_us_total / max(self.n_pool_steps, 1)

    # -------------------------------------------------------- checkpoint
    def save(self, session: int | None = None, blocking: bool = True):
        """Checkpoint one session (returns its step id) or all (list of
        ids).  ``blocking=False`` snapshots state now and hands the disk
        write to the async writer thread."""
        idxs = list(range(self.n)) if session is None else [int(session)]
        self._flush(idxs)
        steps = []
        for i in idxs:
            d = self.sessions[i]
            if blocking:
                steps.append(d.save(blocking=True))
            else:
                if d._mgr is None:
                    raise RuntimeError("no checkpoint_dir configured")
                step = d._save_step
                d._save_step = step + 1
                tree = jax.device_get(d._tree())     # snapshot NOW
                meta = {"n_submitted": d.n_submitted,
                        "decisions": list(d.decisions),
                        "metrics": d.metrics.snapshot()}
                self._writer.submit(d._mgr.save, step, tree,
                                    metadata=meta, blocking=True)
                steps.append(step)
        return steps if session is None else steps[0]

    def restore(self, session: int | None = None,
                step: int | None = None):
        """Restore one session (or all) from its namespaced checkpoints;
        the lane resumes bit-identically (tests/test_service_pool.py).
        Returns per-call success (all-True for the pool form)."""
        idxs = list(range(self.n)) if session is None else [int(session)]
        if any(self._buffers[i] for i in idxs):
            raise RuntimeError("restore with buffered submissions pending "
                               "— drive or drop them first")
        self._writer.flush()             # pending async saves land first
        ok = [self.sessions[i].restore(step) for i in idxs]
        for i in idxs:
            self._horizons[i] = np.float32(self.sessions[i].now)
        self._restack()
        return all(ok) if session is None else ok[0]

    # ----------------------------------------------------------- closing
    def close(self):
        """Drain the writer (decision log + async checkpoints) and close
        the log sink.  Idempotent."""
        if self._log_f is not None:
            self._writer.submit(self._log_f.flush)
        self._writer.close()
        if self._log_f is not None:
            self._log_f.close()
            self._log_f = None
        for d in self.sessions:
            if d._mgr is not None:
                d._mgr.wait()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __repr__(self):
        return (f"SessionPool(n={self.n}, "
                f"queue={self.sessions[0].policy.queue or 'fcfs'!r}, "
                f"capacity={self.capacity}, steps={self.n_pool_steps})")
