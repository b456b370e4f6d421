"""Long-lived dispatcher: the event core's carry as live service state.

The batch engine folds ``step(ctx, carry, horizon)`` through
``lax.scan``; the dispatcher jits the SAME step once and calls it per
event, holding the carry between calls.  Three operations drive a
session:

  submit(prog, arrival)   register a job (fills the next slot of the
                          capacity-padded job arrays; fixed shapes, so
                          the jitted step never retraces; a session over
                          a workload with per-job columns also takes the
                          job's ``t_scale`` and ``nodes``);
  drive(until)            advance the clock through pushes / placements
                          / event hops, never past ``until`` (the step's
                          horizon gate) — returns the decisions emitted;
  drain()                 drive with an open horizon until quiescent.

Fed a workload's stream submit-before-drive-past (each job submitted
before the clock is driven past its arrival), the decision sequence and
final totals are bit-identical to the batch ``Scheduler.run`` — the
extra quiescent steps a live session sees are no-ops on the carry
(asserted in tests/test_service.py).  ``save``/``restore`` persist the
carry + job arrays + realized decisions through ``CheckpointManager``
(atomic npz + msgpack), so a killed session resumes mid-stream with
identical remaining decisions.
"""

from __future__ import annotations

import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.engine import (
    BIG, FaultConfig, Scheduler, Workload, _fault_vec, _job_nodes,
    _power_totals, _workload_arrays, cons_carry0, event_carry0,
    event_context, make_cons_step, make_event_step,
)
from repro.core.policy import Policy
from repro.core.result import SimResult
from repro.checkpoint.manager import CheckpointManager
from repro.service.metrics import ServiceMetrics


def own_columns(w: Workload, j: int) -> dict:
    """Job ``j``'s per-job columns of ``w`` (``Workload.t_scale``,
    ``n_req_job``) as ``Dispatcher.submit`` takes them: what a live replay
    of a trace passes so that each job runs as it does in the batch run.
    Empty for a workload without them."""
    out = {}
    if w.t_scale is not None:
        out["t_scale"] = float(w.t_scale[j])
    if w.n_req_job is not None:
        out["nodes"] = w.n_req_job[j]
    return out


class Dispatcher:
    """A stateful scheduling session over one facility description.

    ``w`` supplies the program x system tables (runtimes, energies, node
    counts, idle watts, outages); its job stream is only a catalog — the
    session's jobs are whatever ``submit`` registers, up to ``capacity``
    (default: the catalog's length).  Where ``w`` has per-job columns
    (``t_scale``, ``n_req_job``), so does the session: each submission
    then carries its job's own runtime scale and node counts.

    Construction: ``Dispatcher.from_scheduler(sched, w, ...)`` is the
    one path — a configured batch ``Scheduler`` IS the session spec
    (policy with queue/cap/tier knobs applied, placer, fault model,
    seed, warm start), so the live session re-declares nothing.  The
    legacy keyword signature survives as a thin shim that builds the
    ``Scheduler`` for you.  Policy leaves must be scalars (a grid has no
    live interpretation).  ``checkpoint_dir`` arms save/restore
    (``checkpoint_namespace`` sub-scopes it — the pool gives every
    session its own).
    """

    def __init__(self, w: Workload, policy: str | Policy = "paper", *,
                 capacity: int | None = None, seed: int = 0,
                 fault: FaultConfig | None = None, placer: str | None = None,
                 warm_start: bool = False, queue: str | None = None,
                 power_cap=None, checkpoint_dir: str | None = None,
                 keep_n: int = 3):
        # thin forwarding shim: every session knob a Scheduler already
        # declares is declared THERE (ISSUE 9 api_redesign)
        sched = Scheduler(
            policy, placer=placer, faults=fault, seeds=int(seed),
            warm_start=warm_start, queue=queue, power_cap=power_cap)
        self._setup(sched, w, capacity=capacity,
                    checkpoint_dir=checkpoint_dir, keep_n=keep_n)

    @classmethod
    def from_scheduler(cls, sched: Scheduler, w: Workload, *,
                       capacity: int | None = None,
                       seed: int | None = None,
                       checkpoint_dir: str | None = None,
                       keep_n: int = 3,
                       checkpoint_namespace: str | None = None
                       ) -> "Dispatcher":
        """The single construction path (CLI, ``SessionPool``, tests):
        adopt a batch ``Scheduler``'s full configuration as the live
        session spec.  ``seed`` overrides the scheduler's scalar seed;
        grid-valued schedulers (seed/fault axes, leaf-batched policies)
        are rejected — a live session is one point."""
        self = cls.__new__(cls)
        self._setup(sched, w, capacity=capacity, seed=seed,
                    checkpoint_dir=checkpoint_dir, keep_n=keep_n,
                    checkpoint_namespace=checkpoint_namespace)
        return self

    def _setup(self, sched: Scheduler, w: Workload, *,
               capacity=None, seed=None, checkpoint_dir=None, keep_n=3,
               checkpoint_namespace=None):
        if isinstance(sched.faults, tuple):
            raise ValueError("live sessions take one FaultConfig, not a "
                             "fault grid")
        if not isinstance(sched.seeds, (int, np.integer)):
            raise ValueError("live sessions take one seed, not a grid")
        pol = sched.policy
        for leaf in ("k", "ucb_scale", "power_cap", "freq_weight"):
            if np.asarray(getattr(pol, leaf)).ndim:
                raise ValueError(f"live policy leaf {leaf!r} must be a "
                                 "scalar, got a grid")
        self.scheduler = sched
        self.policy = pol
        self.seed = int(sched.seeds if seed is None else seed)
        self.fault = sched.faults
        self.placer = sched.placer
        self.capacity = int(capacity) if capacity else max(len(w.prog), 1)
        self.w = w

        fault = self.fault
        self._fvec = _fault_vec(fault or FaultConfig())
        self._retries = bool(fault and fault.failure_prob > 0)
        arrs = _workload_arrays(w)
        C = self.capacity
        arrs["prog"] = jnp.zeros(C, jnp.int32)
        arrs["arrival"] = jnp.full(C, BIG, jnp.float32)
        arrs["k_job"] = jnp.full(C, jnp.nan, jnp.float32)
        S = len(w.n_nodes)
        if "t_scale" in arrs:
            arrs["t_scale"] = jnp.ones(C, jnp.float32)
        if "n_req_job" in arrs:
            arrs["n_req_job"] = jnp.ones((C, S), jnp.int32)
        #: the per-job channels a submission fills
        self.job_keys = ("prog", "arrival", "k_job") + tuple(
            k for k in ("t_scale", "n_req_job") if k in arrs)
        self._arrs = arrs
        self._n_out = (arrs["outage"][..., 1].size
                       if "outage" in arrs else 0)

        P, S = w.T_true.shape
        if sched.warm_start:
            tabs0 = (jnp.asarray(w.C_true), jnp.asarray(w.T_true),
                     jnp.ones((P, S), jnp.int32))
        else:
            tabs0 = (jnp.zeros((P, S)), jnp.zeros((P, S)),
                     jnp.zeros((P, S), jnp.int32))
        self.warm_start = bool(sched.warm_start)
        self._tabs0 = tabs0

        if pol.queue == "conservative":
            build, carry0 = make_cons_step, cons_carry0
        else:
            build, carry0 = make_event_step, event_carry0
        # the pool re-invokes the builder with a leaf-batched policy
        # under vmap — expose it alongside the concrete-leaf closure
        self._build_step = build
        step = build(pol, self.placer, totals_only=False,
                     retries=self._retries)
        self._step_fn = step
        self._step = jax.jit(step)
        # live sessions open at t=0 (the batch scan opens at the first
        # arrival; the extra advances to reach it are carry no-ops)
        self._carry = carry0(self._arrs, pol, tabs0, totals_only=False,
                             now0=0.0)
        self._ctx = event_context(self._arrs, pol, self.seed, self._fvec)

        self.n_submitted = 0
        self.metrics = ServiceMetrics()
        self.decisions: list[dict] = []
        # realized per-job channels, accumulated exactly as
        # ``_event_results`` scatters the scan's ys (f32 adds in step
        # order), so ``result()`` totals match the batch run bitwise
        self._E = np.zeros(C, np.float32)
        self._sys = np.zeros(C, np.int32)
        self._s0 = np.zeros(C, np.float32)
        self._fin = np.zeros(C, np.float32)
        self._wait = np.zeros(C, np.float32)
        self._T = np.ones(C, np.float32)
        self._bf = np.zeros(C, bool)
        self._tier = np.zeros(C, np.int32)

        self._mgr = (CheckpointManager(checkpoint_dir, keep_n=keep_n,
                                       namespace=checkpoint_namespace)
                     if checkpoint_dir else None)
        self._save_step = 0

    # ------------------------------------------------------------ intake
    def _validate_intake(self, prog: int, t: float, *, queued: int = 0,
                         last: float | None = None):
        """The submit-time checks, shared with the pool's buffered
        intake (``queued``/``last`` describe its not-yet-flushed
        buffer)."""
        if self.n_submitted + queued >= self.capacity:
            raise RuntimeError(f"session full: capacity {self.capacity}")
        if not 0 <= int(prog) < self.w.T_true.shape[0]:
            raise ValueError(f"prog {prog} not in the facility catalog "
                             f"(P={self.w.T_true.shape[0]})")
        if t < self.now:
            raise ValueError(f"arrival {t} is in the past (now={self.now})")
        if last is None and self.n_submitted:
            last = float(self._arrs["arrival"][self.n_submitted - 1])
        if last is not None and t < last:
            raise ValueError("submissions must be arrival-ordered")

    def job_row(self, prog: int, t: float, k: float | None = None,
                t_scale: float | None = None, nodes=None) -> dict:
        """One submission's values of ``job_keys``.  ``t_scale`` (its
        runtime over its class's, default 1) and ``nodes`` (its node
        count on each system, default its class's) are taken only by a
        session over a workload with per-job columns."""
        row = {"prog": int(prog), "arrival": t,
               "k_job": np.nan if k is None else float(k)}
        if "t_scale" in self.job_keys:
            row["t_scale"] = 1.0 if t_scale is None else float(t_scale)
            if not (np.isfinite(row["t_scale"]) and row["t_scale"] > 0):
                raise ValueError(f"t_scale {t_scale!r}: finite and positive")
        elif t_scale is not None:
            raise ValueError("t_scale needs a session over a workload with "
                             "per-job runtimes (Workload.t_scale)")
        if "n_req_job" in self.job_keys:
            n = np.asarray(self.w.n_req[int(prog)] if nodes is None
                           else nodes, np.int64)
            if n.shape != (len(self.w.n_nodes),) or (n < 1).any() or (
                    n > np.asarray(self.w.n_nodes)).any():
                raise ValueError(f"nodes {nodes!r}: one count in "
                                 f"[1, n_nodes[s]] per system")
            row["n_req_job"] = n
        elif nodes is not None:
            raise ValueError("nodes needs a session over a workload with "
                             "per-job widths (Workload.n_req_job)")
        return row

    def submit(self, prog: int, arrival: float | None = None,
               k: float | None = None, *, t_scale: float | None = None,
               nodes=None) -> int:
        """Register a job: program index, submit time (default: the
        current clock), optional per-job K override, and in a session
        with per-job columns the job's ``t_scale`` and ``nodes``
        (``job_row``).  Returns the job id.  Submitting an arrival
        earlier than the clock is an error — the past is already
        decided."""
        t = float(self.now if arrival is None else arrival)
        self._validate_intake(prog, t)
        row = self.job_row(prog, t, k, t_scale, nodes)
        j = self.n_submitted
        a = self._arrs
        for key, v in row.items():
            a[key] = a[key].at[j].set(v)
        self._ctx = event_context(a, self.policy, self.seed, self._fvec)
        self.n_submitted += 1
        self.metrics.observe_submit()
        return j

    # ------------------------------------------------------------- clock
    @property
    def now(self) -> float:
        return float(self._carry.now)

    def step_once(self, horizon: float = BIG) -> dict:
        """One event step under ``horizon``; returns the decision record
        (numpy scalars) and folds it into the metrics stream."""
        t0 = time.perf_counter()
        carry, out = self._step(self._ctx, self._carry,
                                jnp.float32(horizon))
        out = jax.device_get(out)
        dt_us = (time.perf_counter() - t0) * 1e6
        self._carry = carry
        self._record(out)
        self.metrics.observe_step(out, dt_us)
        return out

    def _record(self, out: dict):
        """Fold one step's decision channels into the realized per-job
        arrays — the live twin of the ``_event_results`` scatter."""
        C = self.capacity
        if bool(out["placed"]):
            ja = int(out["j_add"])
            if ja < C:
                self._E[ja] += np.float32(out["E"])
        if bool(out["final"]):
            jf = int(out["j_fin"])
            if jf < C:
                self._sys[jf] = out["sys"]
                self._s0[jf] = out["s0"]
                self._fin[jf] = out["finish"]
                self._wait[jf] = out["wait"]
                self._T[jf] = out["T"]
                self._bf[jf] = out["bf"]
                self._tier[jf] = out["tier"]
                self.decisions.append({
                    "job": jf, "system": int(out["sys"]),
                    "start": float(out["s0"]), "finish": float(out["finish"]),
                    "wait": float(out["wait"]),
                    "backfilled": bool(out["bf"]),
                    "tier": int(out["tier"]),
                    "power": float(out["power"]), "now": float(out["now"]),
                })

    def drive(self, until: float = BIG) -> list[dict]:
        """Step until quiescent under ``until``: no push, no placement,
        no clock advance.  Returns the placement decisions emitted."""
        n0 = len(self.decisions)
        limit = 16 * self.capacity + self._n_out + 64
        for _ in range(limit):
            out = self.step_once(until)
            if not (bool(out["pushed"]) or bool(out["placed"])
                    or bool(out["advanced"])):
                break
        else:
            raise RuntimeError("drive() exceeded its step budget — the "
                               "carry is diverging (engine bug)")
        return self.decisions[n0:]

    def drain(self) -> list[dict]:
        """Run the session to completion (open horizon)."""
        return self.drive(BIG)

    # ------------------------------------------------------------ result
    def result(self) -> SimResult:
        """The realized session as a ``SimResult`` over the submitted
        jobs — totals computed with the batch epilogue's jnp expressions
        over the accumulated per-job channels, under one jit (the power
        totals' multiply-subtract must fuse exactly as it does inside
        the batch scan's graph), so a full session matches
        ``Scheduler.run`` bitwise (tests/test_service.py)."""
        n = self.n_submitted
        arrs, carry = self._arrs, self._carry

        @partial(jax.jit, static_argnames=("n",))
        def totals(E, wait, T_act, finish, busy, peak, cdel, n):
            makespan = finish.max() if n else jnp.float32(0.0)
            return dict(
                total_energy=E.sum(), makespan=makespan,
                total_wait=wait.sum(),
                slowdown_sum=((wait + T_act) / T_act).sum(),
                max_wait=wait.max() if n else jnp.float32(0.0),
                **_power_totals(arrs, makespan, busy, peak, cdel))

        E = jnp.asarray(self._E[:n])
        wait = jnp.asarray(self._wait[:n])
        T_act = jnp.asarray(self._T[:n])
        finish = jnp.asarray(self._fin[:n])
        sel = jnp.asarray(self._sys[:n])
        prog = arrs["prog"][:n]
        tot = totals(E, wait, T_act, finish, carry.busy, carry.peak,
                     carry.cdel, n)
        return SimResult(
            **tot,
            busy=carry.busy, C_tab=carry.C_tab, T_tab=carry.T_tab,
            runs=carry.runs,
            n_backfilled=carry.nbf,
            system=sel, start=jnp.asarray(self._s0[:n]), finish=finish,
            wait=wait, energy=E, runtime=T_act,
            nodes=_job_nodes({k: v[:n] if k == "n_req_job" else v
                              for k, v in arrs.items()}, prog, sel),
            backfilled=jnp.asarray(self._bf[:n]),
            tier=jnp.asarray(self._tier[:n]),
            axes=(), n_jobs=n, n_nodes=np.asarray(self.w.n_nodes),
            programs=self.w.programs, systems=self.w.systems,
            freq_tiers=self.policy.freq_tiers)

    def carry_snapshot(self):
        """Host copy of the live carry (tests pin what-if purity on it)."""
        return jax.device_get(self._carry)

    # -------------------------------------------------------- checkpoint
    def _tree(self):
        return {
            "carry": self._carry,
            "jobs": {k: self._arrs[k] for k in self.job_keys},
            "perjob": {"E": self._E, "sys": self._sys, "s0": self._s0,
                       "fin": self._fin, "wait": self._wait, "T": self._T,
                       "bf": self._bf, "tier": self._tier},
        }

    def save(self, blocking: bool = True) -> int:
        """Checkpoint the session (atomic; see checkpoint/manager.py).
        Returns the checkpoint step id."""
        if self._mgr is None:
            raise RuntimeError("no checkpoint_dir configured")
        step = self._save_step
        self._mgr.save(step, self._tree(), metadata={
            "n_submitted": self.n_submitted,
            "decisions": self.decisions,
            "metrics": self.metrics.snapshot(),
        }, blocking=blocking)
        self._save_step = step + 1
        return step

    def restore(self, step: int | None = None) -> bool:
        """Restore the latest (or a specific) checkpoint into this
        session; returns False when the directory holds none.  The
        resumed session's remaining decisions are bit-identical to an
        uninterrupted run (tests/test_service.py)."""
        if self._mgr is None:
            raise RuntimeError("no checkpoint_dir configured")
        tree, step, meta = self._mgr.restore(self._tree(), step)
        if tree is None:
            return False
        self._carry = jax.tree.map(jnp.asarray, tree["carry"])
        for k in self.job_keys:
            self._arrs[k] = jnp.asarray(tree["jobs"][k])
        self._ctx = event_context(self._arrs, self.policy, self.seed,
                                  self._fvec)
        pj = tree["perjob"]
        self._E, self._sys, self._s0 = pj["E"], pj["sys"], pj["s0"]
        self._fin, self._wait, self._T = pj["fin"], pj["wait"], pj["T"]
        self._bf, self._tier = pj["bf"], pj["tier"]
        self.n_submitted = int(meta["n_submitted"])
        self.decisions = list(meta["decisions"])
        self.metrics = ServiceMetrics.from_snapshot(meta["metrics"])
        self._save_step = step + 1
        return True

    def __repr__(self):
        return (f"Dispatcher(queue={self.policy.queue or 'fcfs'!r}, "
                f"jobs={self.n_submitted}/{self.capacity}, "
                f"now={self.now:.1f}, placed={len(self.decisions)})")
