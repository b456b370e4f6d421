"""Campaign-scale scheduling engine: one jitted core, one ``Scheduler`` facade.

Models the paper's SCC: several computing systems (CC_1..CC_S), each a pool
of interchangeable nodes with per-node free-times; a global job queue routed
by a meta-scheduler (a ``repro.core.policy.Policy``).  Jobs are programs
with known per-system ground-truth (T, C, E) from the phase model.

The facade::

    res = Scheduler("paper", seeds=range(4)).run(workload)        # seed axis
    res = Scheduler(make_policy("ucb", k=k_grid, ucb_scale=u_grid),
                    faults=fault_list).run(workload)    # fault x policy grid

``Scheduler.run`` flattens the (fault x policy x seed) grid to one batch
axis, vmaps the lax.scan core over it inside a single jit, and reshapes
back into a structured ``SimResult``/``CampaignResult`` with named axes.
Because Policy hyperparameters (K, ucb_scale) are PyTree *leaves*, a whole
policy-hyperparameter grid shares one compilation — the static policy
metadata (exploration/feasibility/objective) is the only thing that
retraces.

``totals_only=True`` keeps the per-job accounting in the scan carry instead
of materializing [*grid, J] placement arrays — a 10^5-job x large-grid
campaign returns [*grid] aggregates in O(grid) memory.

Placement hot path: the per-step question "when are n_req[s] nodes of
system s free?" is the n_req-th smallest entry of the node-free row,
radix-selected directly (repro.kernels.kth_free: Pallas kernel on TPU,
pure-jnp twin elsewhere, O(S·maxN) per step and bit-exact against the sort
oracle); nodes are allocated by thresholding against that value.

Fault model (DESIGN.md §7): per-job deterministic pseudo-random straggler
slowdowns and node-failure restarts (checkpoint-restart semantics: a failed
job re-does ``restart_overhead`` of its work; energy scales accordingly).
The learned (C, T) tables absorb these — the paper's history mechanism
routes around chronically degraded systems automatically.

Maintenance/outage windows (scenario library, repro.data.scenarios): a
system accepts no new placements while a window [t0, t1) is open; jobs
whose earliest start falls inside a window are pushed to its end.  Windows
must be sorted by start and non-overlapping per system.  Jobs already
running ride through (drain semantics).

Accounting notes: energy is attributed per job (allocated nodes over the
job's span, paper eq. 2); idle energy of unallocated nodes is not attributed
to the suite (the paper compares job-attributed energy).  Learned-table
updates apply as each job is *placed* (the paper stores them at completion;
for the paper's simultaneous-submission experiment the two coincide —
distinct programs never wait on each other's profile entries).
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, replace
from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.dvfs import npb_phase_split, phase_split, tier_tables
from repro.core.policy import (BIG, UNCAPPED, Policy, apply_queue_spec,
                               make_policy, select, select_batched)
from repro.core.result import SimResult, CampaignResult
from repro.core.workload_model import NPB_PROFILES, npb_tables
from repro.kernels.kth_free import (kth_free_time, kth_free_time_rows,
                                    kth_free_time_shared)
from repro.obs import record as _record_programs, stage as _stage
from repro.sharding.grid import (grid_spec as _grid_spec,
                                 replicated as _replicated)


@dataclass(frozen=True)
class SimConfig:
    """Legacy single-run configuration (mode string + fault fields).

    The ``Scheduler`` facade supersedes this for new code; it survives for
    the ``simulate_jax``/``sweep_k``/``run_campaign`` shims and the python
    differential mirror.  ``mode`` accepts any registered policy name.
    """
    mode: str = "paper"
    k: float = 0.0                 # allowed runtime-increase fraction
    straggler_prob: float = 0.0
    straggler_factor: float = 2.0
    failure_prob: float = 0.0
    restart_overhead: float = 0.5
    seed: int = 0
    # True => profile tables pre-filled with ground truth (the paper's
    # Figs 1-4 regime: 'all 5 previously run programs', Tables 3-4 full).
    warm_start: bool = False
    # kth-free placement dispatch: None = auto (Pallas on TPU, jnp radix
    # select elsewhere); or force "pallas"/"pallas_interpret"/"jnp"/"sort".
    placer: str | None = None
    # queue-discipline overrides; "" / 0 defer to the registered policy's
    # own metadata (so mode="easy_backfill" backfills out of the box)
    queue: str = ""
    queue_window: int = 0
    # SCC power cap (Watts); inf = uncapped.  A finite cap routes onto the
    # event-granular core.  Must ride the built policy's leaf so the
    # sweep_k/run_campaign shims pass it through (ISSUE 5 regression).
    power_cap: float = float("inf")
    # scan granularity override: "" = auto ("events" for conservative /
    # capped, "arrival" otherwise), or "arrival" / "events" explicitly.
    core: str = ""

    def policy(self) -> Policy:
        pol = make_policy(self.mode, k=self.k)
        over = {}
        if self.queue:
            over["queue"] = self.queue
        if self.queue_window:
            over["window"] = self.queue_window
        if self.power_cap != float("inf"):
            over["power_cap"] = float(self.power_cap)
        return replace(pol, **over) if over else pol


@dataclass(frozen=True)
class FaultConfig:
    """One point of a fault grid."""
    straggler_prob: float = 0.0
    straggler_factor: float = 2.0
    failure_prob: float = 0.0
    restart_overhead: float = 0.5


@dataclass(frozen=True)
class Workload:
    """Static description of a job stream over P programs x S systems."""
    prog: np.ndarray            # [J] int32 program ids
    arrival: np.ndarray         # [J] f32 submit times
    k_job: np.ndarray           # [J] f32 per-job K (fraction); NaN -> global k
    n_req: np.ndarray           # [P, S] nodes needed
    T_true: np.ndarray          # [P, S] runtime ground truth
    C_true: np.ndarray          # [P, S] J/Mop ground truth
    E_true: np.ndarray          # [P, S] Joules ground truth
    T_pred: np.ndarray          # [P, S] phase-model predictions
    C_pred: np.ndarray
    n_nodes: np.ndarray         # [S] node counts
    programs: tuple = ()        # names, for reports
    systems: tuple = ()
    # [S, W, 2] maintenance windows (start, end), sorted, non-overlapping
    # per system; None = no outages.
    outage: np.ndarray | None = None
    # [S] per-node idle watts (systems.py power model); None = 0 W (no
    # idle draw, power metrics degenerate to job-attributed power only).
    idle_w: np.ndarray | None = None
    # [P, S] compute-phase seconds / dynamic compute joules — the
    # DVFS-sensitive share of T_true / E_true (core/dvfs.py tier model).
    # None = engine defaults (dvfs.phase_split): the whole runtime is
    # compute-phase and every non-idle joule is dynamic.
    T_comp: np.ndarray | None = None
    E_comp: np.ndarray | None = None
    # per-job columns (a trace replay's own runtimes and widths; None = each
    # job runs as its class, see ``_job_scales``):
    # [J] f32 the job's realised runtime over its class's reference runtime
    t_scale: np.ndarray | None = None
    # [J, S] i32 the job's node count on each system
    n_req_job: np.ndarray | None = None


def make_npb_workload(systems, order=("BT", "EP", "IS", "LU", "SP"),
                      arrivals=None, k_job=None, repeats: int = 1,
                      pred_noise: float = 0.0, noise_seed: int = 0,
                      outage=None):
    """The paper's experiment: NPB suite submitted (simultaneously by
    default) to the four JSCC systems. ``repeats`` re-submits the suite."""
    programs = tuple(sorted(set(order)))
    pidx = {p: i for i, p in enumerate(programs)}
    C, T, N = npb_tables(systems, programs)
    mops = np.array([NPB_PROFILES[p].flops / 1e6 for p in programs])
    E = C * mops[:, None]
    rng = np.random.default_rng(noise_seed)
    noise = (1.0 + pred_noise * rng.standard_normal(C.shape)) if pred_noise else 1.0
    seq = list(order) * repeats
    J = len(seq)
    T_comp, E_comp = npb_phase_split(systems, programs, N)
    return Workload(
        prog=np.array([pidx[p] for p in seq], np.int32),
        arrival=np.zeros(J, np.float32) if arrivals is None
        else np.asarray(arrivals, np.float32),
        k_job=np.full(J, np.nan, np.float32) if k_job is None
        else np.asarray(k_job, np.float32),
        n_req=N, T_true=T, C_true=C, E_true=E,
        T_pred=T * noise, C_pred=C * noise,
        n_nodes=np.array([s.n_nodes for s in systems], np.int32),
        programs=programs, systems=tuple(s.name for s in systems),
        outage=None if outage is None else np.asarray(outage, np.float32),
        idle_w=np.array([s.idle_w for s in systems], np.float32),
        T_comp=T_comp, E_comp=E_comp,
    )


def _fault_factor(key, j, fvec):
    """fvec: [straggler_prob, straggler_factor, failure_prob, restart_ovh]."""
    with _stage("fault"):
        u = jax.random.uniform(jax.random.fold_in(key, j), (2,))
        slow = jnp.where(u[0] < fvec[0], fvec[1], 1.0)
        fail = jnp.where(u[1] < fvec[2], 1.0 + fvec[3], 1.0)
        return slow * fail


def _workload_arrays(w: Workload) -> dict:
    """Workload -> the jnp pytree the jitted core consumes."""
    max_n = int(w.n_nodes.max())
    node_exists = np.arange(max_n)[None, :] < w.n_nodes[:, None]   # [S, maxN]
    arrs = {
        "free0": jnp.where(jnp.asarray(node_exists), 0.0, BIG),
        "prog": jnp.asarray(w.prog),
        "arrival": jnp.asarray(w.arrival),
        "k_job": jnp.asarray(w.k_job),
        "n_req": jnp.asarray(w.n_req),
        "T_true": jnp.asarray(w.T_true),
        "C_true": jnp.asarray(w.C_true),
        "E_true": jnp.asarray(w.E_true),
        "T_pred": jnp.asarray(w.T_pred),
        "C_pred": jnp.asarray(w.C_pred),
        # power model: per-job average draw (paper eq. 1-2: the phase
        # components integrate to E, so E/T is the job's step-function
        # contribution to the cluster trace) + per-system idle watts
        "w_pow": jnp.asarray(w.E_true / np.maximum(w.T_true, 1e-30),
                             jnp.float32),
        "idle_w": jnp.zeros(len(w.n_nodes), jnp.float32)
        if w.idle_w is None else jnp.asarray(w.idle_w, jnp.float32),
    }
    # DVFS tier model inputs (explicit phase split, or the trace-workload
    # defaults — see dvfs.phase_split); consumed only under freq_tiers
    T_comp, E_comp = phase_split(w)
    arrs["T_comp"] = jnp.asarray(T_comp, jnp.float32)
    arrs["E_comp"] = jnp.asarray(E_comp, jnp.float32)
    if w.outage is not None and w.outage.size:
        arrs["outage"] = jnp.asarray(w.outage, jnp.float32)
    J, S = len(w.prog), len(w.n_nodes)
    if w.t_scale is not None:
        ts = _column(w.t_scale, (J,), "t_scale")
        if not (np.isfinite(ts) & (ts > 0)).all():
            raise ValueError("t_scale must be finite and positive")
        arrs["t_scale"] = jnp.asarray(ts, jnp.float32)
    if w.n_req_job is not None:
        nj = _column(w.n_req_job, (J, S), "n_req_job")
        if (nj < 1).any() or (nj > np.asarray(w.n_nodes)[None, :]).any():
            raise ValueError("n_req_job must lie in [1, n_nodes[s]] on "
                             "every system")
        arrs["n_req_job"] = jnp.asarray(nj, jnp.int32)
    return arrs


def _column(x, shape, name):
    x = np.asarray(x)
    if x.shape != shape:
        raise ValueError(f"{name} has shape {x.shape}, the workload needs "
                         f"{shape}")
    return x


def _job_need(arrs, j, p, sel=None):
    """Nodes job ``j`` of class ``p`` asks of each system ([..., S]), or of
    system ``sel``: its own width where the workload has per-job widths,
    else its class's."""
    if "n_req_job" not in arrs:
        return arrs["n_req"][p] if sel is None else arrs["n_req"][p, sel]
    with _stage("job"):
        return arrs["n_req_job"][j] if sel is None \
            else arrs["n_req_job"][j, sel]


def _job_t_scale(arrs, j):
    """Job ``j``'s realised runtime over its class's; None where the
    workload has no per-job runtimes."""
    if "t_scale" not in arrs:
        return None
    with _stage("job"):
        return arrs["t_scale"][j]


def _job_scales(arrs, j, p, sel):
    """What turns job ``j``'s class values on system ``sel`` into its own:
    ``(runtime, energy, draw)`` multipliers ``(t_scale_j, t_scale_j * w,
    w)`` with ``w = n_req_job[j, sel] / n_req[p, sel]`` its width over its
    class's.
    Work (``C``) scales with node-seconds and is left as the class's; the
    learned tables keep learning per class.  None where the workload has
    no per-job columns: its jobs run as their class, and the step
    compiles as it does without this helper."""
    if "t_scale" not in arrs and "n_req_job" not in arrs:
        return None
    ts = _job_t_scale(arrs, j)
    with _stage("job"):
        if ts is None:
            ts = jnp.float32(1.0)
        if "n_req_job" not in arrs:
            return ts, ts, jnp.float32(1.0)
        wr = (arrs["n_req_job"][j, sel].astype(jnp.float32)
              / arrs["n_req"][p, sel].astype(jnp.float32))
        return ts, ts * wr, wr


def _running_mean(mean, n, x, scales):
    """A learned mean after its ``n + 1``-th observation ``x``.  With
    per-job columns (``scales``) both operands of the running sum's add
    take a trip through the integer domain first, so that no compiler can
    fuse a product into that add as one fused multiply-add: one program
    built from a step (the batch scan) might contract where another (a
    live session's step) does not, and per-job observations, themselves
    products, would leave the two programs' tables an ulp apart.  An
    ``optimization_barrier`` does not hold here: XLA:CPU drops it before
    fusing."""
    total = mean * n
    if scales is not None:
        with _stage("job"):
            # n is a run count, never negative: the or adds no bit, and
            # the compiler cannot know it
            sign = (n < 0.0).astype(jnp.int32)
            total, x = (jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(v, jnp.int32) | sign,
                jnp.float32) for v in (total, x))
    return (total + x) / (n + 1)


def _job_nodes(arrs, prog, sel):
    """[J] nodes each job held on its system ``sel``."""
    if "n_req_job" in arrs:
        return jnp.take_along_axis(arrs["n_req_job"], sel[:, None],
                                   axis=1)[:, 0]
    return arrs["n_req"][prog, sel]


def _push_out_of_outage(avail, outage):
    """Earliest start per system, pushed past any open maintenance window.
    Windows sorted by start per system, so one in-order pass resolves
    cascades (a push landing inside the next window is pushed again).
    ``avail``'s last axis is the system axis (leading axes broadcast)."""
    for wi in range(outage.shape[1]):
        o0, o1 = outage[:, wi, 0], outage[:, wi, 1]
        avail = jnp.where((avail >= o0) & (avail < o1), o1, avail)
    return avail


def _earliest(node_free, nreq_row, arr, placer, outage):
    """(kth free time, earliest start) per system for one job: the kth-free
    radix select, floored at the arrival and pushed out of any open
    maintenance window.  Shared by the FCFS step, the EASY reservation /
    backfill guard, and the final placement."""
    with _stage("earliest"):
        kth = kth_free_time(node_free, nreq_row, force=placer)
        avail = jnp.maximum(arr, kth)
        if outage is not None:
            avail = _push_out_of_outage(avail, outage)
        return kth, avail


def _earliest_shared(node_free, nreq_rows, arr_col, placer, outage):
    """``_earliest`` for a whole candidate batch against ONE node-free
    table: [W, S] requests -> ([W, S] kth, [W, S] earliest start), via the
    shared-table kernel entry (one sort serves every candidate).
    ``arr_col``: [W, 1] per-candidate arrival floors."""
    with _stage("earliest"):
        kth = kth_free_time_shared(node_free, nreq_rows, force=placer)
        avail = jnp.maximum(arr_col, kth)
        if outage is not None:
            avail = _push_out_of_outage(avail, outage)
        return kth, avail


def _alloc_mask(node_free, sel, kth_sel, need):
    """The nodes ``_alloc`` takes on system ``sel``: everything strictly
    below the kth free time, plus first-by-index ties at it (the python
    mirror's stable argsort picks the same nodes).  Exposed separately so
    the event core can mirror an allocation onto its node-power table."""
    with _stage("alloc"):
        free_sel = node_free[sel]
        below = free_sel < kth_sel
        tie = free_sel == kth_sel
        tie_rank = jnp.cumsum(tie) - 1
        return below | (tie & (tie_rank < need - jnp.sum(below)))


def _alloc(node_free, sel, kth_sel, need, finish):
    """Allocate the ``need`` earliest-free nodes of system ``sel`` until
    ``finish`` (see ``_alloc_mask`` for the tie-break)."""
    with _stage("alloc"):
        take = _alloc_mask(node_free, sel, kth_sel, need)
        return node_free.at[sel].set(jnp.where(take, finish, node_free[sel]))


def _idle_energy(arrs, makespan, busy):
    """Idle draw of UNallocated existing nodes over the makespan (Joules).
    Job-attributed energy already covers allocated nodes' idle component
    (predict_energy integrates idle_w over the job span), so this is the
    complement the paper's site-level power view adds."""
    idle_w = arrs["idle_w"]                                      # [S]
    n_exist = jnp.sum(arrs["free0"] < BIG, axis=1)               # [S]
    return (jnp.sum(idle_w * n_exist) * makespan
            - jnp.sum(idle_w * busy))


def _tier_rows(tt, p, C_row, T_row, runs_row, avail_row, C_pred_row,
               T_pred_row):
    """Expand one job's (or a [W]-batched set of) selection rows over the
    (tier x system) candidate axis, tier-major (flat index = f * S + s,
    so tier 0 / phi = 1.0 occupies the first S entries and argmin
    tie-breaks anchor at full frequency).

    ``tt`` is the ``tier_tables`` dict; learned rows and predictions are
    scaled by the per-tier energy/runtime ratios (unit ratios are exactly
    1.0, so tier-0 entries are the base rows bit for bit), run counts are
    tier-independent (tables always learn base observations), and
    ``avail_row`` is tiled when per-system ([..., S]) or flattened when
    already per-(tier, system) ([..., F, S] — the conservative core's
    per-tier earliest-fit)."""
    rc, rt = tt["rc"][p], tt["rt"][p]                    # [..., F, S]
    F, S = rc.shape[-2], rc.shape[-1]
    flat = lambda x: x.reshape(x.shape[:-2] + (F * S,))
    tile = lambda x: flat(jnp.broadcast_to(x[..., None, :],
                                           x.shape[:-1] + (F, S)))
    avail_x = flat(avail_row) if avail_row.shape == rc.shape \
        else tile(avail_row)
    return (flat(C_row[..., None, :] * rc), flat(T_row[..., None, :] * rt),
            tile(runs_row), avail_x,
            flat(C_pred_row[..., None, :] * rc),
            flat(T_pred_row[..., None, :] * rt))


class _SimPieces(NamedTuple):
    """One simulation, disassembled for streamed execution:
    ``lax.scan(step, carry0, xs, length=length)`` followed by
    ``finish(carry, ys)`` IS ``_scan_sim`` — same step trace, same
    epilogue ops.  The chunked driver (``_run_chunked``) instead slices
    ``xs`` into fixed windows, threads the carry between per-chunk scans
    and reassembles spilled ``ys`` before the shared finish, so chunked
    results are bit-identical to the monolithic scan by construction."""
    step: object      # step(carry, x) -> (carry, out)
    xs: object        # [length]-leading scan inputs, or None (event cores)
    length: int       # static step count
    carry0: object    # initial carry
    finish: object    # finish(carry, ys) -> result dict


def _stream_xs(arrs: dict, policy: Policy, core: str = "arrival",
               retries: bool = False):
    """Scan inputs + static step count of the routed core, buildable on
    the host (the chunked driver slices these without constructing the
    full pieces).  Step counts: the event core needs one push + one
    placement per job and every advance lands on a distinct event time,
    so ``4J + |outage| + 4`` steps suffice (``7J`` with retries: a
    failure adds one push, one placement, one event); the conservative
    core's reservation starts add at most one advance each (``5J`` /
    ``9J``).  The arrival xs carry the RAW per-job K column — steps
    resolve NaN -> policy.k at use, so no per-lane [J] K vector ever
    materializes under the batched vmap."""
    J = arrs["prog"].shape[0]
    n_out = arrs["outage"][..., 1].size if "outage" in arrs else 0
    if policy.queue == "conservative":
        return None, (9 if retries else 5) * J + n_out + 4
    if core == "events":
        return None, (7 if retries else 4) * J + n_out + 4
    if policy.queue == "easy_backfill":
        W = int(policy.window)
        jxs = jnp.concatenate([jnp.arange(J, dtype=jnp.int32),
                               jnp.full((W,), J, jnp.int32)])
        nows = jnp.concatenate([arrs["arrival"],
                                jnp.full((W,), BIG, jnp.float32)])
        return (jxs, nows), J + W
    return (jnp.arange(J), arrs["prog"], arrs["arrival"], arrs["k_job"]), J


def _sim_pieces(arrs: dict, policy: Policy, warm_start: bool,
                placer: str | None, totals_only: bool, seed, fvec,
                easy_eval: str = "batched", core: str = "arrival",
                retries: bool = False) -> _SimPieces:
    """Build one simulation's pieces; every argument traced except the
    static (policy metadata, warm_start, placer, totals_only, easy_eval,
    core, retries).  Dispatch:

    - ``core="arrival"`` (default): the historical arrival-indexed scans —
      the FCFS path bit-identical to the pre-queue-axis engine, EASY via
      the windowed scan (``_easy_pieces``);
    - ``core="events"`` (or ``queue="conservative"``, which requires it):
      the event-granular step folded with an open horizon — the core that
      can defer placements under an SCC power cap and re-queue mid-job
      failures (``retries``).
    """
    T_true, C_true = arrs["T_true"], arrs["C_true"]
    P, S = T_true.shape
    # independent streams for selection and fault draws — folding a shared
    # key with j and j+offset would collide once J exceeds the offset,
    # which campaign streams (10k+ jobs) do
    sel_key, fault_key = jax.random.split(jax.random.key(seed))

    if warm_start:
        tabs0 = (C_true, T_true, jnp.ones((P, S), jnp.int32))
    else:
        tabs0 = (jnp.zeros((P, S)), jnp.zeros((P, S)),
                 jnp.zeros((P, S), jnp.int32))
    xs, length = _stream_xs(arrs, policy, core, retries)

    if policy.queue == "conservative" or core == "events":
        cons = policy.queue == "conservative"
        estep = (make_cons_step if cons else make_event_step)(
            policy, placer, totals_only, retries)
        ctx = {"arrs": arrs, "sel_key": sel_key, "fault_key": fault_key,
               "fvec": fvec}
        if policy.tiered:
            ctx["tt"] = tier_tables(arrs, policy.freq_tiers)
        hor = jnp.float32(BIG)
        carry0 = (cons_carry0 if cons else event_carry0)(
            arrs, policy, tabs0, totals_only)
        return _SimPieces(
            lambda c, _: estep(ctx, c, hor), xs, length, carry0,
            lambda carry, ys: _event_results(arrs, totals_only, ys, carry))
    if policy.queue == "easy_backfill":
        step, carry0, fin = _easy_pieces(arrs, policy, placer, totals_only,
                                         sel_key, fault_key, fvec, tabs0,
                                         easy_eval)
    else:
        step, carry0, fin = _arrival_pieces(arrs, policy, placer,
                                            totals_only, sel_key,
                                            fault_key, fvec, tabs0)
    return _SimPieces(step, xs, length, carry0, fin)


def _scan_sim(arrs: dict, policy: Policy, warm_start: bool,
              placer: str | None, totals_only: bool, seed, fvec,
              easy_eval: str = "batched", core: str = "arrival",
              retries: bool = False):
    """One full simulation: fold the routed core's pieces through a
    single lax.scan (see ``_sim_pieces`` for dispatch and staticness)."""
    pieces = _sim_pieces(arrs, policy, warm_start, placer, totals_only,
                         seed, fvec, easy_eval, core, retries)
    carry, ys = jax.lax.scan(pieces.step, pieces.carry0, pieces.xs,
                             length=pieces.length)
    return pieces.finish(carry, ys)


def _arrival_pieces(arrs: dict, policy: Policy, placer: str | None,
                    totals_only: bool, sel_key, fault_key, fvec, tabs0):
    """Pieces of the arrival-indexed FCFS scan (the historical core)."""
    T_true, C_true, E_true = arrs["T_true"], arrs["C_true"], arrs["E_true"]
    T_pred, C_pred = arrs["T_pred"], arrs["C_pred"]
    n_req, prog, arrival = arrs["n_req"], arrs["prog"], arrs["arrival"]
    outage = arrs.get("outage")
    P, S = T_true.shape
    J = prog.shape[0]
    tiered = policy.tiered
    tt = tier_tables(arrs, policy.freq_tiers) if tiered else None
    pol_k = jnp.asarray(policy.k, jnp.float32)

    def step(carry, xs):
        node_free, C_tab, T_tab, runs, acc = carry
        j, p, arr, kj = xs
        with _stage("select"):
            # per-job effective K: explicit workload overrides win over
            # the policy's (resolved at use — the xs carry the raw
            # NaN-padded column, see _stream_xs)
            k = jnp.where(jnp.isnan(kj), pol_k, kj)

        with _stage("earliest"):
            nreq_row = _job_need(arrs, j, p)                     # [S]
            kth, avail = _earliest(node_free, nreq_row, arr, placer,
                                   outage)

        with _stage("select"):
            key = jax.random.fold_in(sel_key, j)
            if tiered:
                c_x, t_x, r_x, a_x, cp_x, tp_x = _tier_rows(
                    tt, p, C_tab[p], T_tab[p], runs[p], avail, C_pred[p],
                    T_pred[p])
                sel_x = select(policy, c_row=c_x, t_row=t_x, runs_row=r_x,
                               avail_row=a_x, k=k, c_pred_row=cp_x,
                               t_pred_row=tp_x, key=key)
                f = (sel_x // S).astype(jnp.int32)
                sel = sel_x % S
            else:
                f = jnp.int32(0)
                sel = select(
                    policy, c_row=C_tab[p], t_row=T_tab[p],
                    runs_row=runs[p], avail_row=avail, k=k,
                    c_pred_row=C_pred[p], t_pred_row=T_pred[p], key=key)

        with _stage("fault"):
            factor = _fault_factor(fault_key, j, fvec)
            # tables learn base (tier-0) observations — a tier choice
            # changes the realized runtime/energy, never the learned
            # profile
            C_act = C_true[p, sel] * factor
            T_upd = T_true[p, sel] * factor
            if tiered:
                T_act = tt["T"][p, f, sel] * factor
                E_act = tt["E"][p, f, sel] * factor
            else:
                T_act = T_upd
                E_act = E_true[p, sel] * factor
        scales = _job_scales(arrs, j, p, sel)
        if scales is not None:
            with _stage("job"):
                T_upd, T_act = T_upd * scales[0], T_act * scales[0]
                E_act = E_act * scales[1]

        with _stage("alloc"):
            start = avail[sel]
            finish = start + T_act
            need = nreq_row[sel]
            node_free = _alloc(node_free, sel, kth[sel], need, finish)

        with _stage("learn"):
            n = runs[p, sel].astype(jnp.float32)
            C_tab = C_tab.at[p, sel].set(
                _running_mean(C_tab[p, sel], n, C_act, scales))
            T_tab = T_tab.at[p, sel].set(
                _running_mean(T_tab[p, sel], n, T_upd, scales))
            runs = runs.at[p, sel].add(1)

        with _stage("account"):
            wait = start - arr
            if totals_only:
                sums, comps, fin_max, busy, wait_max = acc
                # Kahan-compensated f32 sums: 10^5 sequential adds would
                # otherwise drift ~0.1% vs the full path's array
                # reduction (x64 is unavailable, so compensation stands
                # in for f64)
                add = jnp.stack([E_act, wait, (wait + T_act) / T_act])
                y = add - comps
                t = sums + y
                acc = (t, (t - sums) - y, jnp.maximum(fin_max, finish),
                       busy.at[sel].add(T_act * need),
                       jnp.maximum(wait_max, wait))
                out = None
            else:
                out = (sel, start, finish, wait, E_act, T_act, f)
        return (node_free, C_tab, T_tab, runs, acc), out

    acc0 = ((jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.float32),
             jnp.float32(0.0), jnp.zeros(S, jnp.float32),
             jnp.float32(0.0))
            if totals_only else ())
    carry0 = (arrs["free0"], *tabs0, acc0)

    def finish(carry, ys):
        node_free, C_tab, T_tab, runs, acc = carry
        tabs = {"C_tab": C_tab, "T_tab": T_tab, "runs": runs,
                "n_backfilled": jnp.zeros((), jnp.int32)}
        if totals_only:
            sums, _, fin_max, busy, wait_max = acc
            return {"total_energy": sums[0], "makespan": fin_max,
                    "total_wait": sums[1], "slowdown_sum": sums[2],
                    "max_wait": wait_max, "busy": busy,
                    **_power_totals(arrs, fin_max, busy), **tabs}
        sel, start, fin, wait, E, T_act, tier = ys
        nodes = _job_nodes(arrs, prog, sel)                      # [J]
        busy = jnp.zeros(S, jnp.float32).at[sel].add(T_act * nodes)
        makespan = fin.max()
        return {
            "system": sel, "start": start, "finish": fin, "wait": wait,
            "energy": E, "runtime": T_act, "nodes": nodes, "tier": tier,
            "backfilled": jnp.zeros(J, bool),
            "total_energy": E.sum(), "makespan": makespan,
            "total_wait": wait.sum(), "max_wait": wait.max(),
            "slowdown_sum": ((wait + T_act) / T_act).sum(), "busy": busy,
            **_power_totals(arrs, makespan, busy), **tabs,
        }

    return step, carry0, finish


def _power_totals(arrs, makespan, busy, peak_power=None, capped_delay=None):
    """The SCC power fields every result carries.  The arrival-indexed
    scans do not track a cluster power trace (placements may carry future
    starts, so no running peak exists): they report ``peak_power`` NaN and
    zero ``capped_delay``; ``idle_energy`` is derivable from busy
    node-seconds on every core."""
    return {
        "peak_power": jnp.float32(jnp.nan) if peak_power is None
        else peak_power,
        "capped_delay": jnp.float32(0.0) if capped_delay is None
        else capped_delay,
        "idle_energy": _idle_energy(arrs, makespan, busy),
    }


def _easy_pieces(arrs: dict, policy: Policy, placer: str | None,
                 totals_only: bool, sel_key, fault_key, fvec, tabs0,
                 easy_eval: str = "batched"):
    """EASY-backfilling scan: J + W steps over a bounded pending window.

    The carry grows a pending buffer of W + 1 job-id slots (ascending,
    padded with the sentinel J).  Each step pushes the arriving job (steps
    past J are the drain tail) and places AT MOST one job:

      1. the head (oldest pending) — forced when the window overflows
         (FCFS fallback), or placed when its reserved start ``r_h`` (policy
         selection over current node-free times) is <= ``now``, the latest
         arrival time (BIG during the drain, so the tail drains FCFS);
      2. otherwise the first pending job (arrival order) whose tentative
         allocation does not push the head's earliest start on its
         reserved system past ``r_h`` — the EASY no-delay reservation
         guard.  (No "starts now" requirement: the scan's only events are
         arrivals, so a backfill may carry a future start — it fills the
         gap under the reservation exactly as an event-driven EASY would
         at the next completion event.)
      3. or nothing: the head keeps waiting for a backfill opportunity.

    Because at most one job is placed per step and a full window forces a
    head placement, every job is placed within J + W steps.  Placement
    math (kth-free selection, allocation tie-breaks, table updates, fault
    draws keyed by job id) is shared with the FCFS step, so ``fcfs`` and
    ``easy_backfill`` differ only in placement ORDER, never in per-job
    semantics.  Per-step outputs carry (job id | sentinel); the full path
    scatters them back into arrival-indexed [J] arrays after the scan.

    Candidate evaluation (``easy_eval``, static): every trial allocation
    in a step is computed against the SAME starting node-free table, so
    the W + 1 slots are independent and the first-fit choice is a masked
    argmin over slot index.  ``"batched"`` (default) scores all slots in
    one shared-table [W+1, S] kth-free call (``kth_free_time_shared`` —
    one sort serves every candidate) + one vmapped ``select`` + one
    vmapped tentative allocation; the no-delay guard then needs only the
    head's RESERVED system, so one per-row kth query over the trials'
    ``sel_h`` rows ([W+1, maxN]) rechecks every candidate at once — two
    batched kernel calls per step instead of ~2W sequential radix walks.
    ``"unrolled"`` is the historical python-unrolled loop, kept as the
    bit-identity reference (``tests/test_easy_batched.py`` asserts the
    two agree exactly across the whole policy registry).
    """
    T_true, C_true, E_true = arrs["T_true"], arrs["C_true"], arrs["E_true"]
    T_pred, C_pred = arrs["T_pred"], arrs["C_pred"]
    n_req, prog, arrival = arrs["n_req"], arrs["prog"], arrs["arrival"]
    outage = arrs.get("outage")
    P, S = T_true.shape
    J = prog.shape[0]
    W = int(policy.window)
    Wc = W + 1                           # buffer capacity (push-then-place)
    tiered = policy.tiered
    if tiered and easy_eval != "batched":
        raise ValueError("freq_tiers requires easy_eval='batched' (the "
                         "unrolled loop predates the tier axis and exists "
                         "only as the single-tier bit-identity reference)")
    tt = tier_tables(arrs, policy.freq_tiers) if tiered else None
    k_job = arrs["k_job"]
    pol_k = jnp.asarray(policy.k, jnp.float32)

    def k_of(j):
        """Per-job effective K at use (NaN -> the policy leaf); no [J]
        K vector materializes per batch lane."""
        kj = k_job[j]
        return jnp.where(jnp.isnan(kj), pol_k, kj)

    def sel_for(j, node_free, C_tab, T_tab, runs):
        """Policy selection + earliest start for job id j (sentinel-safe:
        j == J evaluates job J-1; callers mask the result)."""
        with _stage("earliest"):
            jj = jnp.minimum(j, J - 1)
            p = prog[jj]
            kth, avail = _earliest(node_free, _job_need(arrs, jj, p),
                                   arrival[jj], placer, outage)
        with _stage("select"):
            sel = select(
                policy, c_row=C_tab[p], t_row=T_tab[p], runs_row=runs[p],
                avail_row=avail, k=k_of(jj), c_pred_row=C_pred[p],
                t_pred_row=T_pred[p], key=jax.random.fold_in(sel_key, jj))
        return jj, p, kth, avail, sel

    def eval_candidates(node_free, C_tab, T_tab, runs, pend):
        """Score every pending slot against the SAME node-free table in
        one batched pass (sentinel slots evaluate job J-1; callers mask).
        Returns per-slot [Wc]-leading arrays: job ids, programs, chosen
        systems, starts, actual runtimes, fault factors, node needs, and
        the [Wc, S, maxN] tentative-allocation stack."""
        with _stage("earliest"):
            jjs = jnp.minimum(pend, J - 1)                        # [Wc]
            ps = prog[jjs]                                        # [Wc]
            kths, avails = _earliest_shared(
                node_free, _job_need(arrs, jjs, ps), arrival[jjs][:, None],
                placer, outage)                                   # [Wc, S]
        with _stage("select"):
            keys = jax.vmap(lambda j: jax.random.fold_in(sel_key, j))(jjs)
            if tiered:
                c_x, t_x, runs_x, avail_x, cp_x, tp_x = _tier_rows(
                    tt, ps, C_tab[ps], T_tab[ps], runs[ps], avails,
                    C_pred[ps], T_pred[ps])
                sels_x = select_batched(
                    policy, c_rows=c_x, t_rows=t_x, runs_rows=runs_x,
                    avail_rows=avail_x, k=k_of(jjs), c_pred_rows=cp_x,
                    t_pred_rows=tp_x, keys=keys)                  # [Wc]
                fs = (sels_x // S).astype(jnp.int32)
                sels = sels_x % S
            else:
                sels = select_batched(
                    policy, c_rows=C_tab[ps], t_rows=T_tab[ps],
                    runs_rows=runs[ps], avail_rows=avails, k=k_of(jjs),
                    c_pred_rows=C_pred[ps], t_pred_rows=T_pred[ps],
                    keys=keys)                                    # [Wc]
                fs = jnp.zeros(Wc, jnp.int32)
        factors = jax.vmap(lambda j: _fault_factor(fault_key, j, fvec))(jjs)
        with _stage("alloc"):
            idx = jnp.arange(Wc)
            starts = avails[idx, sels]                            # [Wc]
        with _stage("fault"):
            T_acts = (tt["T"][ps, fs, sels] if tiered
                      else T_true[ps, sels]) * factors
        scales = _job_scales(arrs, jjs, ps, sels)
        if scales is not None:
            with _stage("job"):
                T_acts = T_acts * scales[0]
        with _stage("alloc"):
            needs = _job_need(arrs, jjs, ps, sels)
            trials = jax.vmap(_alloc, in_axes=(None, 0, 0, 0, 0))(
                node_free, sels, kths[idx, sels], needs, starts + T_acts)
        return jjs, ps, sels, fs, starts, T_acts, factors, needs, trials

    def step(carry, xs):
        node_free, C_tab, T_tab, runs, acc, pend, nbf = carry
        jx, now = xs

        with _stage("push"):
            # push the arrival into the first sentinel slot (the
            # invariant size <= W at step start keeps the index in range;
            # drain steps push the sentinel J over a sentinel — a no-op)
            size0 = jnp.sum(pend < J)
            pend = pend.at[jnp.minimum(size0, Wc - 1)].set(jx)
            size = size0 + (jx < J)
            forced = size == Wc                   # window full: FCFS fallback
            head_valid = pend[0] < J

        if easy_eval == "batched":
            # one batched evaluation of all Wc slots; slot 0 is the head
            jjs, ps, sels, fs, starts, T_acts, factors, needs, trials = \
                eval_candidates(node_free, C_tab, T_tab, runs, pend)
            with _stage("select"):
                hj, p_h, sel_h = jjs[0], ps[0], sels[0]
                r_h = starts[0]                   # head reservation
                place_head = head_valid & (forced | (r_h <= now))

            # EASY no-delay guard for ALL candidates at once: a trial can
            # only delay the head on the head's RESERVED system, so one
            # per-row kth query over the trials' sel_h rows answers every
            # candidate (rows untouched by a trial reproduce r_h exactly,
            # so their guard passes as it must)
            # (every kth mode is bit-exact, so absent an explicit placer
            # the recheck picks the cheapest: one sort op over [Wc, maxN]
            # beats Wc radix walks inside a scan)
            with _stage("earliest"):
                kth_h2 = kth_free_time(
                    trials[:, sel_h, :],
                    jnp.broadcast_to(_job_need(arrs, hj, p_h, sel_h), (Wc,)),
                    force=placer or "sort")
                avail_h2 = jnp.maximum(arrival[hj], kth_h2)       # [Wc]
                if outage is not None:
                    # only sel_h's windows apply; [1, W0, 2] broadcasts
                    # the shared push over the [Wc] candidate vector
                    avail_h2 = _push_out_of_outage(avail_h2,
                                                   outage[sel_h][None])
                ok = avail_h2 <= r_h                              # [Wc]

            with _stage("select"):
                # first-fit == masked argmin over slot index (Wc = none)
                idx = jnp.arange(Wc)
                elig = jnp.where(idx == 0, place_head,
                                 head_valid & ~place_head & (pend < J) & ok)
                chosen = jnp.min(jnp.where(elig, idx, Wc))
                placed = chosen < Wc
                ci = jnp.minimum(chosen, Wc - 1)

            with _stage("alloc"):
                # gather the chosen slot: its trial allocation was
                # computed against the real starting node_free, so it IS
                # the placement
                jj, p, sel, f = jjs[ci], ps[ci], sels[ci], fs[ci]
                factor = factors[ci]
                T_act = T_acts[ci]
                start = starts[ci]
                need = needs[ci]
                j_pl = jnp.where(placed, pend[ci], J)
                node_free = jnp.where(placed, trials[ci], node_free)
        else:
            # head-of-queue reservation from current node-free times
            h = pend[0]
            hj, p_h, _, avail_h, sel_h = sel_for(h, node_free, C_tab, T_tab,
                                                 runs)
            with _stage("select"):
                r_h = avail_h[sel_h]
                place_head = head_valid & (forced | (r_h <= now))

                # EASY backfill: first pending job (arrival order) whose
                # tentative allocation cannot delay the head's
                # reservation on its reserved system
                chosen = jnp.where(place_head, 0, Wc)  # slot; Wc = none
                may_backfill = head_valid & ~place_head
            for ci in range(1, Wc):
                with _stage("select"):
                    b = pend[ci]
                    live = may_backfill & (b < J) & (chosen == Wc)
                bj, p_b, kth_b, avail_b, sel_b = sel_for(b, node_free, C_tab,
                                                         T_tab, runs)
                with _stage("alloc"):
                    s_b = avail_b[sel_b]
                    T_b = T_true[p_b, sel_b] * _fault_factor(
                        fault_key, bj, fvec)
                scales_b = _job_scales(arrs, bj, p_b, sel_b)
                if scales_b is not None:
                    with _stage("job"):
                        T_b = T_b * scales_b[0]
                with _stage("alloc"):
                    fin_b = s_b + T_b
                    trial = _alloc(node_free, sel_b, kth_b[sel_b],
                                   _job_need(arrs, bj, p_b, sel_b), fin_b)
                with _stage("earliest"):
                    _, avail_h2 = _earliest(trial, _job_need(arrs, hj, p_h),
                                            arrival[hj], placer, outage)
                    ok = avail_h2[sel_h] <= r_h
                with _stage("select"):
                    chosen = jnp.where(live & ok, ci, chosen)

            # place the chosen job (if any): same math as the FCFS step
            with _stage("select"):
                placed = chosen < Wc
                j_pl = jnp.where(placed, pend[jnp.minimum(chosen, Wc - 1)],
                                 J)
            jj, p, kth, avail, sel = sel_for(j_pl, node_free, C_tab, T_tab,
                                             runs)
            with _stage("fault"):
                f = jnp.int32(0)                  # unrolled path is untier
                factor = _fault_factor(fault_key, jj, fvec)
                T_act = T_true[p, sel] * factor
            scales = _job_scales(arrs, jj, p, sel)
            if scales is not None:
                with _stage("job"):
                    T_act = T_act * scales[0]
            with _stage("alloc"):
                start = avail[sel]
                need = _job_need(arrs, jj, p, sel)
                node_free = jnp.where(
                    placed,
                    _alloc(node_free, sel, kth[sel], need, start + T_act),
                    node_free)

        with _stage("fault"):
            # learned tables always absorb BASE (tier-0) observations;
            # the recorded energy/runtime use the tier-scaled values
            C_act = C_true[p, sel] * factor
            T_upd = T_true[p, sel] * factor
            E_act = (tt["E"][p, f, sel] if tiered
                     else E_true[p, sel]) * factor
        scales = _job_scales(arrs, jj, p, sel)
        if scales is not None:
            with _stage("job"):
                T_upd, E_act = T_upd * scales[0], E_act * scales[1]
        with _stage("alloc"):
            finish = start + T_act

        with _stage("learn"):
            n = runs[p, sel].astype(jnp.float32)
            C_tab = C_tab.at[p, sel].set(jnp.where(
                placed, _running_mean(C_tab[p, sel], n, C_act, scales),
                C_tab[p, sel]))
            T_tab = T_tab.at[p, sel].set(jnp.where(
                placed, _running_mean(T_tab[p, sel], n, T_upd, scales),
                T_tab[p, sel]))
            runs = runs.at[p, sel].add(jnp.where(placed, 1, 0))

        with _stage("account"):
            was_backfill = placed & (chosen > 0)
            nbf = nbf + was_backfill.astype(jnp.int32)

        with _stage("push"):
            # pop the chosen slot (shift the tail left; chosen == Wc:
            # no-op)
            shifted = jnp.concatenate([pend[1:],
                                       jnp.full((1,), J, jnp.int32)])
            pend = jnp.where(jnp.arange(Wc) < chosen, pend, shifted)

        with _stage("account"):
            wait = start - arrival[jj]
            if totals_only:
                sums, comps, fin_max, busy, wait_max = acc
                add = jnp.where(
                    placed, jnp.stack([E_act, wait, (wait + T_act) / T_act]),
                    0.0)
                y = add - comps
                t = sums + y
                acc = (t, (t - sums) - y,
                       jnp.maximum(fin_max, jnp.where(placed, finish, 0.0)),
                       busy.at[sel].add(jnp.where(placed, T_act * need,
                                                  0.0)),
                       jnp.maximum(wait_max, jnp.where(placed, wait, 0.0)))
                out = None
            else:
                out = (j_pl, sel, start, finish, wait, E_act, T_act,
                       was_backfill, f)
        return (node_free, C_tab, T_tab, runs, acc, pend, nbf), out

    acc0 = ((jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.float32),
             jnp.float32(0.0), jnp.zeros(S, jnp.float32),
             jnp.float32(0.0))
            if totals_only else ())
    pend0 = jnp.full((Wc,), J, jnp.int32)
    carry0 = (arrs["free0"], *tabs0, acc0, pend0, jnp.zeros((), jnp.int32))

    def finish(carry, ys):
        node_free, C_tab, T_tab, runs, acc, pend, nbf = carry
        tabs = {"C_tab": C_tab, "T_tab": T_tab, "runs": runs,
                "n_backfilled": nbf}
        if totals_only:
            sums, _, fin_max, busy, wait_max = acc
            return {"total_energy": sums[0], "makespan": fin_max,
                    "total_wait": sums[1], "slowdown_sum": sums[2],
                    "max_wait": wait_max, "busy": busy,
                    **_power_totals(arrs, fin_max, busy), **tabs}

        # scatter per-step outputs back to arrival order; sentinels drop
        j_pl, sel_s, start_s, fin_s, wait_s, E_s, T_s, bf_s, f_s = ys
        def scat(vals, dtype):
            return jnp.zeros(J, dtype).at[j_pl].set(vals, mode="drop")
        sel = scat(sel_s, sel_s.dtype)
        start = scat(start_s, jnp.float32)
        fin = scat(fin_s, jnp.float32)
        wait = scat(wait_s, jnp.float32)
        E = scat(E_s, jnp.float32)
        T_act = scat(T_s, jnp.float32)
        backfilled = scat(bf_s, bool)
        tier = scat(f_s, jnp.int32)
        nodes = _job_nodes(arrs, prog, sel)                      # [J]
        busy = jnp.zeros(S, jnp.float32).at[sel].add(T_act * nodes)
        makespan = fin.max()
        return {
            "system": sel, "start": start, "finish": fin, "wait": wait,
            "energy": E, "runtime": T_act, "nodes": nodes,
            "backfilled": backfilled, "tier": tier,
            "total_energy": E.sum(), "makespan": makespan,
            "total_wait": wait.sum(), "max_wait": wait.max(),
            "slowdown_sum": ((wait + T_act) / T_act).sum(), "busy": busy,
            **_power_totals(arrs, makespan, busy), **tabs,
        }

    return step, carry0, finish


class EventCarry(NamedTuple):
    """Live state of the event-granular core between two ``make_event_step``
    calls.  A NamedTuple (still an ordinary pytree to scan/jit) so the
    service dispatcher and the checkpoint manifest address fields by name.
    """
    node_free: jnp.ndarray   # [S, maxN] node free-from times
    node_pow: jnp.ndarray    # [S, maxN] per-node allocated draw (Watts)
    C_tab: jnp.ndarray       # [P, S] learned energy coefficients
    T_tab: jnp.ndarray       # [P, S] learned runtimes
    runs: jnp.ndarray        # [P, S] observation counts
    acc: tuple               # Kahan totals accumulator (empty if full path)
    busy: jnp.ndarray        # [S] busy node-seconds
    pend: jnp.ndarray        # [Wc] pending job ids (J = sentinel)
    t0s: jnp.ndarray         # [Wc] effective arrivals
    rts: jnp.ndarray         # [Wc] retry flags
    accTs: jnp.ndarray       # [Wc] accrued runtime of failed attempts
    accFs: jnp.ndarray       # [Wc] accrued fault factor
    accWs: jnp.ndarray       # [Wc] accrued wait
    s0s: jnp.ndarray         # [Wc] first-attempt starts
    pblocks: jnp.ndarray     # [Wc] first power-blocked times (BIG = never)
    a: jnp.ndarray           # next-arrival cursor
    now: jnp.ndarray         # event clock
    nbf: jnp.ndarray         # backfill count
    peak: jnp.ndarray        # running peak cluster draw
    cdel: jnp.ndarray        # cap-attributed placement delay


def event_context(arrs: dict, policy: Policy, seed, fvec) -> dict:
    """The traced per-run inputs of the factored event steps (everything a
    step reads besides its carry): workload arrays and the selection /
    fault PRNG keys — derived exactly as ``_sim_pieces`` derives them, so
    a service session shares the batch scan's streams.  The ``kvec`` entry
    (precomputed per-job effective K) is retained for checkpoint/back-
    compat; steps resolve K at use from ``arrs["k_job"]`` and the policy
    leaf (elementwise identical), so no [J] K vector rides the hot path."""
    kvec = jnp.where(jnp.isnan(arrs["k_job"]),
                     jnp.asarray(policy.k, jnp.float32), arrs["k_job"])
    sel_key, fault_key = jax.random.split(jax.random.key(seed))
    ctx = {"arrs": arrs, "kvec": kvec, "sel_key": sel_key,
           "fault_key": fault_key, "fvec": fvec}
    if policy.tiered:
        ctx["tt"] = tier_tables(arrs, policy.freq_tiers)
    return ctx


def event_carry0(arrs: dict, policy: Policy, tabs0, totals_only: bool,
                 now0=None) -> EventCarry:
    """The event core's initial carry.  ``now0`` overrides the starting
    clock (the batch scan opens at the first arrival; a live dispatcher
    opens at 0 and advances to the first submission)."""
    S = arrs["T_true"].shape[1]
    J = arrs["prog"].shape[0]
    Wc = int(policy.window) + 1
    idle_total = jnp.where(arrs["free0"] < BIG,
                           arrs["idle_w"][:, None], 0.0).sum()
    acc0 = ((jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.float32),
             jnp.float32(0.0), jnp.float32(0.0))
            if totals_only else ())
    if now0 is None:
        now0 = arrs["arrival"][0]
    return EventCarry(
        node_free=arrs["free0"], node_pow=jnp.zeros_like(arrs["free0"]),
        C_tab=tabs0[0], T_tab=tabs0[1], runs=tabs0[2], acc=acc0,
        busy=jnp.zeros(S, jnp.float32),
        pend=jnp.full((Wc,), J, jnp.int32), t0s=jnp.zeros(Wc, jnp.float32),
        rts=jnp.zeros(Wc, bool), accTs=jnp.zeros(Wc, jnp.float32),
        accFs=jnp.zeros(Wc, jnp.float32), accWs=jnp.zeros(Wc, jnp.float32),
        s0s=jnp.zeros(Wc, jnp.float32),
        pblocks=jnp.full((Wc,), BIG, jnp.float32),
        a=jnp.int32(0), now=jnp.asarray(now0, jnp.float32),
        nbf=jnp.int32(0), peak=idle_total, cdel=jnp.float32(0.0))


def make_event_step(policy: Policy, placer: str | None = None,
                    totals_only: bool = False, retries: bool = False):
    """Event-granular step: the clock advances through the merged stream
    of arrival AND completion events, so the pending buffer is
    re-evaluated whenever nodes free up.

    Carry: node-free AND node-power tables, learned tables, a pending
    buffer of ``window + 1`` slots (job id + per-slot effective arrival /
    retry flag / accrued runtime / accrued fault factor / accrued wait /
    first-attempt start / first-power-blocked time), the next-arrival
    cursor ``a``, the clock ``now``, and the power accumulators (running
    peak, cap-attributed delay).  Each step performs at least one of:

      push     admit the next arrival (``arrival[a] <= now`` and the
               buffer has room; a full buffer stalls admission — arrivals
               wait OUTSIDE the window, so no placement is ever forced
               with a future start and the power cap stays enforceable);
      place    at most one pending job whose start is feasible *now*:
               resource-feasible (earliest start <= now), discipline-
               eligible, and power-feasible (below).  Eligibility by
               ``policy.queue``:
                 fcfs          the head only — placements in strict
                               arrival order (bit-identical to the
                               arrival-indexed scan, asserted per
                               registered policy);
                 easy_backfill head, or any slot whose tentative
                               allocation cannot delay the head's
                               reservation (event-driven EASY: backfills
                               start at the current event, never in the
                               future);
               (``conservative`` runs its own event-granular step,
               ``make_cons_step`` — reservations chained through a
               profile table instead of per-step re-evaluation);
      advance  otherwise move ``now`` to the next event: the earliest of
               the next arrival, the earliest node-free time > now (a
               completion), or the next outage end.

    Every job needs one push + one placement and every advance lands on a
    distinct event time, so ``4J + |outage| + 4`` steps suffice (``7J``
    with retries: a failure adds one push, one placement, one event).

    Power-cap enforcement (``policy.power_cap``, a LEAF — cap grids batch
    in one jit): the carry's node-power table gives the cluster draw
    ``P(now) = sum(busy ? node_pow : idle_w)``; a placement converting
    ``need`` idle nodes to a job drawing ``E/T`` Watts is deferred while
    ``P(now) - need*idle_w + E/T > cap``.  Under a finite cap starts are
    quantized to the current event (``start = now``), so the recorded
    trace is exact and ``peak_power <= cap`` holds whenever the cap is
    above the idle floor (a cap below the all-idle draw is unsatisfiable;
    the head is force-placed rather than stalling forever, and the
    recorded peak honestly exceeds the cap).  Uncapped runs keep the
    resource-earliest start (possibly before ``now`` — nodes were idle
    since then), which preserves FCFS bit-identity; ``peak_power`` is
    then the draw sampled at placement instants.  ``capped_delay`` sums,
    over placed jobs, the gap between the first time a job was the next
    would-be placement but power-blocked and its actual start.

    Mid-job failures (``retries=True``, chosen by the facade when a fault
    grid carries ``failure_prob > 0``): instead of the arrival cores'
    contiguous ``(1 + restart_overhead)`` inflation, the first attempt of
    a failing job occupies its nodes for ``restart_overhead`` of its work
    and then RE-QUEUES through the same pending buffer (effective arrival
    = the failure time, a completion event like any other).  The retry
    re-selects a system with current tables and never fails again.
    Tables update once, at the final attempt, with the job's accumulated
    fault factor — for a same-system retry exactly the contiguous
    model's ``(1 + restart_overhead)`` totals.

    Factored form (the online-service refactor): this builder returns the
    bare ``step(ctx, carry, horizon) -> (carry, out)`` callable — ``ctx``
    from ``event_context``, ``carry`` from ``event_carry0``.  The batch
    scan (``_sim_pieces``) folds it through ``lax.scan`` with
    ``horizon = BIG`` (bit-identical to the pre-refactor closure, asserted
    across tests/test_event_core.py); the service dispatcher jits it once
    and calls it per event with a finite horizon, which only gates the
    clock: ``advance`` never moves ``now`` past ``horizon`` (so a live
    session cannot run ahead of arrivals it has not been told about) and
    the stuck valve stays closed under a finite horizon (waiting for the
    operator to drive further is always legal).  With ``horizon = BIG``
    both gates are no-ops, so the batch op sequence is unchanged.  The
    full-path ``out`` is a dict: the batch-result channels consumed by
    ``_event_results`` plus live-decision extras (pushed/placed/advanced
    flags, realized start, post-step clock, queue depth, cluster draw).
    """
    W = int(policy.window)
    Wc = W + 1
    queue = policy.queue
    tiered = policy.tiered
    idx = jnp.arange(Wc)

    def step(ctx, carry, horizon):
        arrs, fvec = ctx["arrs"], ctx["fvec"]
        sel_key, fault_key = ctx["sel_key"], ctx["fault_key"]
        tt = ctx["tt"] if tiered else None
        T_true, C_true, E_true = (arrs["T_true"], arrs["C_true"],
                                  arrs["E_true"])
        T_pred, C_pred = arrs["T_pred"], arrs["C_pred"]
        n_req, prog, arrival = arrs["n_req"], arrs["prog"], arrs["arrival"]
        outage = arrs.get("outage")
        w_pow, idle_w = arrs["w_pow"], arrs["idle_w"]
        # per-job effective K at use (NaN -> the policy leaf; elementwise
        # identical to the historical precomputed kvec gather, without a
        # per-lane [J] intermediate)
        pol_k = jnp.asarray(policy.k, jnp.float32)
        k_of = lambda j: jnp.where(jnp.isnan(arrs["k_job"][j]), pol_k,
                                   arrs["k_job"][j])
        J = prog.shape[0]
        with _stage("select"):
            exists = arrs["free0"] < BIG                         # [S, maxN]
            idle_mat = jnp.where(exists, idle_w[:, None], 0.0)   # [S, maxN]
            pc = jnp.asarray(policy.power_cap, jnp.float32)
            capped = pc < UNCAPPED                               # traced
        with _stage("advance"):
            out_ends = (None if outage is None
                        else outage[..., 1].reshape(-1))         # [S*W0]

        (node_free, node_pow, C_tab, T_tab, runs, acc, busy,
         pend, t0s, rts, accTs, accFs, accWs, s0s, pblocks,
         a, now, nbf, peak, cdel) = carry

        # ---- push: admit the next arrival if due and there is room
        with _stage("push"):
            size0 = jnp.sum(pend < J)
            arr_a = arrival[jnp.minimum(a, J - 1)]
            do_push = (a < J) & (size0 < Wc) & (arr_a <= now)
            slot = jnp.minimum(size0, Wc - 1)

            def pushed(arr, val):
                return arr.at[slot].set(jnp.where(do_push, val, arr[slot]))
            pend = pushed(pend, a.astype(jnp.int32))
            t0s = pushed(t0s, arr_a)
            rts = pushed(rts, False)
            accTs = pushed(accTs, 0.0)
            accFs = pushed(accFs, 0.0)
            accWs = pushed(accWs, 0.0)
            s0s = pushed(s0s, 0.0)
            pblocks = pushed(pblocks, BIG)
            a = a + do_push

        # ---- next event (pre-placement state; used by advance + the
        # stuck valve).  Completions are node-free times > now.
        with _stage("advance"):
            next_evt = jnp.min(jnp.where(node_free > now, node_free, BIG))
            arr_next = arrival[jnp.minimum(a, J - 1)]
            next_evt = jnp.minimum(
                next_evt,
                jnp.where((a < J) & (arr_next > now), arr_next, BIG))
            if out_ends is not None:
                next_evt = jnp.minimum(
                    next_evt,
                    jnp.min(jnp.where(out_ends > now, out_ends, BIG)))

        # ---- batched evaluation of every pending slot (sentinel slots
        # evaluate job J-1 behind a BIG arrival floor; never eligible)
        with _stage("earliest"):
            valid = pend < J
            jjs = jnp.minimum(pend, J - 1)
            ps = prog[jjs]
            t0f = jnp.where(valid, t0s, BIG)
            kths, avails = _earliest_shared(node_free,
                                            _job_need(arrs, jjs, ps),
                                            t0f[:, None], placer, outage)
        with _stage("select"):
            keys = jax.vmap(lambda j: jax.random.fold_in(sel_key, j))(jjs)
            if tiered:
                S = T_true.shape[1]
                c_x, t_x, runs_x, avail_x, cp_x, tp_x = _tier_rows(
                    tt, ps, C_tab[ps], T_tab[ps], runs[ps], avails,
                    C_pred[ps], T_pred[ps])
                sels_x = select_batched(
                    policy, c_rows=c_x, t_rows=t_x, runs_rows=runs_x,
                    avail_rows=avail_x, k=k_of(jjs), c_pred_rows=cp_x,
                    t_pred_rows=tp_x, keys=keys)                 # [Wc]
                fs = (sels_x // S).astype(jnp.int32)
                sels = sels_x % S
            else:
                sels = select_batched(
                    policy, c_rows=C_tab[ps], t_rows=T_tab[ps],
                    runs_rows=runs[ps], avail_rows=avails, k=k_of(jjs),
                    c_pred_rows=C_pred[ps], t_pred_rows=T_pred[ps],
                    keys=keys)                                   # [Wc]
                fs = jnp.zeros(Wc, jnp.int32)
        with _stage("alloc"):
            starts_res = avails[idx, sels]                       # [Wc]

        # fault draws (keyed by job id, as _fault_factor does)
        with _stage("fault"):
            u = jax.vmap(lambda j: jax.random.uniform(
                jax.random.fold_in(fault_key, j), (2,)))(jjs)    # [Wc, 2]
            slows = jnp.where(u[:, 0] < fvec[0], fvec[1], 1.0)
            fails = u[:, 1] < fvec[2]
            if retries:
                first_fail = fails & ~rts    # retries never fail again
                scale = jnp.where(first_fail, fvec[3], 1.0)
            else:
                first_fail = jnp.zeros(Wc, bool)
                scale = jnp.where(fails, 1.0 + fvec[3], 1.0)
            factors = slows * scale
            T_acts = (tt["T"][ps, fs, sels] if tiered
                      else T_true[ps, sels]) * factors
            E_acts = (tt["E"][ps, fs, sels] if tiered
                      else E_true[ps, sels]) * factors
        scales = _job_scales(arrs, jjs, ps, sels)
        if scales is not None:
            with _stage("job"):
                T_acts = T_acts * scales[0]
                E_acts = E_acts * scales[1]

        with _stage("alloc"):
            needs = _job_need(arrs, jjs, ps, sels)

            # start rule: capped runs quantize to the current event
            # (exact power trace); uncapped keep the resource-earliest
            # start (FCFS bit-identity — the nodes were idle since then)
            starts = jnp.where(capped, jnp.maximum(starts_res, now),
                               starts_res)
            finishes = starts + T_acts
            trials = jax.vmap(_alloc, in_axes=(None, 0, 0, 0, 0))(
                node_free, sels, kths[idx, sels], needs, finishes)

        # ---- discipline eligibility (resource side)
        with _stage("select"):
            res_ok = valid & (starts_res <= now)
            if outage is not None:
                # a cap-deferred start quantizes to ``now`` — which must
                # itself respect the start gate: a slot whose system has
                # an open maintenance window is not placeable until the
                # window ends (an event the clock advances to).  Uncapped
                # starts are already outage-pushed inside ``starts_res``.
                gated = _push_out_of_outage(starts, outage[sels])
                res_ok = res_ok & (~capped | (gated <= now))
        if queue == "fcfs":
            with _stage("select"):
                elig_res = res_ok & (idx == 0)
        else:  # event-driven EASY: only the head's reservation is guarded
            with _stage("earliest"):
                p_h, sel_h = ps[0], sels[0]
                # (the head's job id is read only for a per-job width)
                j_h = jjs[0] if "n_req_job" in arrs else None
                r_h = starts_res[0]
                kth_h2 = kth_free_time(
                    trials[:, sel_h, :],
                    jnp.broadcast_to(_job_need(arrs, j_h, p_h, sel_h),
                                     (Wc,)),
                    force=placer or "sort")
                avail_h2 = jnp.maximum(t0f[0], kth_h2)           # [Wc]
                if outage is not None:
                    avail_h2 = _push_out_of_outage(avail_h2,
                                                   outage[sel_h][None])
            with _stage("select"):
                elig_res = res_ok & ((idx == 0) | (avail_h2 <= r_h))

        # ---- power feasibility + the stuck valve
        with _stage("select"):
            p_now = jnp.sum(jnp.where(node_free > now, node_pow, idle_mat))
            w_jobs = (tt["w"][ps, fs, sels] if tiered
                      else w_pow[ps, sels])                      # [Wc]
        if scales is not None:
            with _stage("job"):
                w_jobs = w_jobs * scales[2]
        with _stage("select"):
            new_P = p_now - needs * idle_w[sels] + w_jobs        # [Wc]
            power_ok = ~capped | (new_P <= pc)
            elig0 = elig_res & power_ok
            head_valid = valid[0]
            # no event ahead + nothing placeable can only mean the cap is
            # below the idle floor: force the head rather than stall
            # forever (only with an open horizon — under a finite one the
            # session is simply waiting to be driven further, never stuck)
            stuck = (head_valid & ~do_push & ~jnp.any(elig0)
                     & (next_evt >= BIG) & (horizon >= BIG))
            elig = jnp.where(idx == 0, elig0[0] | stuck, elig0)

            chosen = jnp.min(jnp.where(elig, idx, Wc))
            placed = chosen < Wc
            ci = jnp.minimum(chosen, Wc - 1)

            # cap-attributed delay: the next would-be placement,
            # power-blocked
            chosen_res = jnp.min(jnp.where(elig_res, idx, Wc))
            cri = jnp.minimum(chosen_res, Wc - 1)
            blocked = (chosen_res < Wc) & ~power_ok[cri]
            pblocks = pblocks.at[cri].set(
                jnp.where(blocked, jnp.minimum(pblocks[cri], now),
                          pblocks[cri]))

        # ---- place the chosen slot (its trial IS the allocation)
        with _stage("alloc"):
            jj, p, sel = jjs[ci], ps[ci], sels[ci]
            factor, T_act, E_act = factors[ci], T_acts[ci], E_acts[ci]
            start, finish, need = starts[ci], finishes[ci], needs[ci]
            failed_now = placed & first_fail[ci]
            final = placed & ~first_fail[ci]
            # per-slot accruals, captured before the pop shifts the buffer
            accT_ci, accF_ci, accW_ci = accTs[ci], accFs[ci], accWs[ci]
            s0_ci = jnp.where(rts[ci], s0s[ci], start)
            wait_step = start - t0s[ci]
            pb_ci = pblocks[ci]

            take = _alloc_mask(node_free, sel, kths[ci, sel], need)
            node_free = jnp.where(placed, trials[ci], node_free)
            per_node = w_jobs[ci] / jnp.maximum(need, 1).astype(jnp.float32)
            node_pow = jnp.where(
                placed,
                node_pow.at[sel].set(jnp.where(take, per_node,
                                               node_pow[sel])),
                node_pow)

        with _stage("learn"):
            fac_tot = accF_ci + factor
            C_upd = C_true[p, sel] * fac_tot
            T_upd = T_true[p, sel] * fac_tot
        if scales is not None:
            with _stage("job"):
                T_upd = T_upd * scales[0][ci]
        with _stage("learn"):
            n = runs[p, sel].astype(jnp.float32)
            C_tab = C_tab.at[p, sel].set(jnp.where(
                final, _running_mean(C_tab[p, sel], n, C_upd, scales),
                C_tab[p, sel]))
            T_tab = T_tab.at[p, sel].set(jnp.where(
                final, _running_mean(T_tab[p, sel], n, T_upd, scales),
                T_tab[p, sel]))
            runs = runs.at[p, sel].add(jnp.where(final, 1, 0))

        with _stage("account"):
            busy = busy.at[sel].add(jnp.where(placed, T_act * need, 0.0))
            nbf = nbf + (final & (chosen > 0)).astype(jnp.int32)
            peak = jnp.maximum(peak, jnp.where(placed, new_P[ci], 0.0))
            cdel = cdel + jnp.where(placed & (pb_ci < BIG), now - pb_ci,
                                    0.0)

        with _stage("push"):
            # pop the chosen slot (shift left; chosen == Wc: no-op)
            def pop(arr, fill):
                shifted = jnp.concatenate(
                    [arr[1:], jnp.full((1,), fill, arr.dtype)])
                return jnp.where(idx < chosen, arr, shifted)
            pend = pop(pend, J)
            t0s, rts = pop(t0s, 0.0), pop(rts, False)
            accTs, accFs, accWs = pop(accTs, 0.0), pop(accFs, 0.0), \
                pop(accWs, 0.0)
            s0s, pblocks = pop(s0s, 0.0), pop(pblocks, BIG)

            if retries:
                # a failed first attempt re-queues at the tail: effective
                # arrival = the failure time (a completion event)
                size2 = jnp.sum(pend < J)
                slot2 = jnp.minimum(size2, Wc - 1)

                def requeue(arr, val):
                    return arr.at[slot2].set(
                        jnp.where(failed_now, val, arr[slot2]))
                pend = requeue(pend, jj.astype(jnp.int32))
                t0s = requeue(t0s, finish)
                rts = requeue(rts, True)
                accTs = requeue(accTs, accT_ci + T_act)
                accFs = requeue(accFs, fac_tot)
                accWs = requeue(accWs, accW_ci + wait_step)
                s0s = requeue(s0s, s0_ci)
                pblocks = requeue(pblocks, BIG)

        with _stage("account"):
            T_tot = accT_ci + T_act
            wait_tot = accW_ci + wait_step

        # ---- advance the clock only when nothing else happened (and
        # never past the horizon)
        with _stage("advance"):
            advance = (~do_push & ~placed & (next_evt < BIG)
                       & (next_evt <= horizon))
            now = jnp.where(advance, next_evt, now)

        with _stage("account"):
            if totals_only:
                sums, comps, fin_max, wait_max = acc
                add = jnp.stack([
                    E_act,
                    jnp.where(final, wait_tot, 0.0),
                    jnp.where(final, (wait_tot + T_tot) / T_tot, 0.0)])
                # Kahan update applied ONLY on placement steps, so the
                # FCFS op sequence matches the arrival-indexed core bit
                # for bit
                y = add - comps
                t = sums + y
                acc = (jnp.where(placed, t, sums),
                       jnp.where(placed, (t - sums) - y, comps),
                       jnp.maximum(fin_max, jnp.where(placed, finish, 0.0)),
                       jnp.maximum(wait_max, jnp.where(final, wait_tot,
                                                       0.0)))
                out = None
            else:
                out = {
                    # batch-result channels (_event_results scatters these)
                    "j_add": jnp.where(placed, jj, J), "E": E_act,
                    "j_fin": jnp.where(final, jj, J), "sys": sel,
                    "s0": s0_ci, "finish": finish, "wait": wait_tot,
                    "T": T_tot, "bf": final & (chosen > 0),
                    "tier": fs[ci],
                    # live-decision channels (the service dispatcher reads
                    # these; pure additions, the batch channels are
                    # untouched)
                    "pushed": do_push,
                    "j_push": jnp.where(do_push, a - 1, J),
                    "placed": placed, "final": final, "advanced": advance,
                    "start": start, "now": now, "qlen": jnp.sum(pend < J),
                    "power": jnp.where(placed, new_P[ci], p_now),
                }

        return EventCarry(
            node_free, node_pow, C_tab, T_tab, runs, acc, busy,
            pend, t0s, rts, accTs, accFs, accWs, s0s, pblocks,
            a, now, nbf, peak, cdel), out

    return step


def _event_results(arrs, totals_only, ys, carry):
    """Shared result epilogue of the two event-granular scans: unpack the
    totals accumulator, or scatter the per-step (attempt-energy,
    final-attempt fields) output channels back to arrival order.  Takes
    the final carry (EventCarry or ConsCarry — same field names)."""
    n_req, prog = arrs["n_req"], arrs["prog"]
    J = prog.shape[0]
    busy, peak, cdel = carry.busy, carry.peak, carry.cdel
    tabs = {"C_tab": carry.C_tab, "T_tab": carry.T_tab, "runs": carry.runs,
            "n_backfilled": carry.nbf}
    if totals_only:
        sums, _, fin_max, wait_max = carry.acc
        return {"total_energy": sums[0], "makespan": fin_max,
                "total_wait": sums[1], "slowdown_sum": sums[2],
                "max_wait": wait_max, "busy": busy,
                **_power_totals(arrs, fin_max, busy, peak, cdel), **tabs}

    j_add, E_s, j_fin = ys["j_add"], ys["E"], ys["j_fin"]
    sel_s, s0_s, fin_s = ys["sys"], ys["s0"], ys["finish"]
    wait_s, T_s, bf_s = ys["wait"], ys["T"], ys["bf"]
    E = jnp.zeros(J, jnp.float32).at[j_add].add(E_s, mode="drop")
    def scat(vals, dtype):
        return jnp.zeros(J, dtype).at[j_fin].set(vals, mode="drop")
    sel = scat(sel_s, sel_s.dtype)
    start = scat(s0_s, jnp.float32)
    finish = scat(fin_s, jnp.float32)
    wait = scat(wait_s, jnp.float32)
    T_act = scat(T_s, jnp.float32)
    backfilled = scat(bf_s, bool)
    tier = scat(ys["tier"], jnp.int32)
    nodes = _job_nodes(arrs, prog, sel)                          # [J]
    makespan = finish.max()
    return {
        "system": sel, "start": start, "finish": finish, "wait": wait,
        "energy": E, "runtime": T_act, "nodes": nodes,
        "backfilled": backfilled, "tier": tier,
        "total_energy": E.sum(), "makespan": makespan,
        "total_wait": wait.sum(), "max_wait": wait.max(),
        "slowdown_sum": ((wait + T_act) / T_act).sum(), "busy": busy,
        **_power_totals(arrs, makespan, busy, peak, cdel), **tabs,
    }


class ConsCarry(NamedTuple):
    """Live state of the conservative event core (``make_cons_step``).
    Field names shared with ``EventCarry`` where semantics coincide; the
    per-slot pending columns live in the ``slots`` dict (job id, timing
    accruals, and the reservation row: system/start/finish/need/...)."""
    node_free: jnp.ndarray   # [S, maxN] node free-from times
    node_pow: jnp.ndarray    # [S, maxN] per-node allocated draw (Watts)
    C_tab: jnp.ndarray       # [P, S] learned energy coefficients
    T_tab: jnp.ndarray       # [P, S] learned runtimes
    runs: jnp.ndarray        # [P, S] observation counts
    acc: tuple               # Kahan totals accumulator (empty if full path)
    busy: jnp.ndarray        # [S] busy node-seconds
    slots: dict              # [Wc]-leading per-slot reservation table
    a: jnp.ndarray           # next-arrival cursor
    now: jnp.ndarray         # event clock
    nbf: jnp.ndarray         # backfill count
    peak: jnp.ndarray        # running peak cluster draw
    cdel: jnp.ndarray        # cap-attributed placement delay


def cons_carry0(arrs: dict, policy: Policy, tabs0, totals_only: bool,
                now0=None) -> ConsCarry:
    """The conservative core's initial carry (see ``event_carry0``)."""
    S = arrs["T_true"].shape[1]
    J = arrs["prog"].shape[0]
    Wc = int(policy.window) + 1
    idle_total = jnp.where(arrs["free0"] < BIG,
                           arrs["idle_w"][:, None], 0.0).sum()
    acc0 = ((jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.float32),
             jnp.float32(0.0), jnp.float32(0.0))
            if totals_only else ())
    if now0 is None:
        now0 = arrs["arrival"][0]
    slots0 = dict(
        pend=jnp.full((Wc,), J, jnp.int32), t0=jnp.zeros(Wc, jnp.float32),
        rt=jnp.zeros(Wc, bool), accT=jnp.zeros(Wc, jnp.float32),
        accF=jnp.zeros(Wc, jnp.float32), accW=jnp.zeros(Wc, jnp.float32),
        s0=jnp.zeros(Wc, jnp.float32),
        pblock=jnp.full((Wc,), BIG, jnp.float32),
        sel=jnp.zeros(Wc, jnp.int32), start=jnp.zeros(Wc, jnp.float32),
        fin=jnp.zeros(Wc, jnp.float32),
        T=jnp.ones(Wc, jnp.float32), E=jnp.zeros(Wc, jnp.float32),
        need=jnp.zeros(Wc, jnp.int32), wjob=jnp.zeros(Wc, jnp.float32),
        fac=jnp.zeros(Wc, jnp.float32), fail=jnp.zeros(Wc, bool),
        tier=jnp.zeros(Wc, jnp.int32))
    return ConsCarry(
        node_free=arrs["free0"], node_pow=jnp.zeros_like(arrs["free0"]),
        C_tab=tabs0[0], T_tab=tabs0[1], runs=tabs0[2], acc=acc0,
        busy=jnp.zeros(S, jnp.float32), slots=slots0,
        a=jnp.int32(0), now=jnp.asarray(now0, jnp.float32),
        nbf=jnp.int32(0), peak=idle_total, cdel=jnp.float32(0.0))


def make_cons_step(policy: Policy, placer: str | None = None,
                   totals_only: bool = False, retries: bool = False):
    """Conservative backfilling: hole-aware chained reservations on the
    event-granular clock.

    Textbook conservative gives EVERY queued job a reservation the moment
    it is admitted, computed around all earlier pending reservations — so
    backfilling is hole-filling by construction and no reservation is
    ever delayed.  Crucially, reservations are NOT committed into the
    node-free table (a free-from time per node cannot represent "idle
    until the reservation starts", which is exactly the hole backfilling
    lives on — committing eagerly is why the arrival-indexed FCFS scan
    wastes those gaps).  Instead the carry keeps:

      node_free      reality — realized placements only;
      the slot reservation table — per pending slot its (system, start,
                     finish, nodes): explicit intervals.

    Admission evaluates, per system, the earliest start where FREE
    CAPACITY (count of really-free nodes minus reservation occupancy)
    covers the job for its whole duration: candidate starts are the
    arrival, node free times and reservation finishes (capacity rises),
    each checked against every reservation start inside the candidate
    window (the only capacity dips).  That [S, E] piecewise-capacity
    evaluation is a handful of vectorized comparisons against the [W]
    reservation table — the admission IS the reservation-table update.
    The policy then selects over the per-system earliest starts and the
    chosen (sel, start, finish, need) joins the table.  Selection thus
    happens at ADMISSION time with the tables as of admission (learned
    tables still update at placement).

    A placement *realizes* a reservation once the clock reaches its
    start: the per-slot realizability recheck (``kth_free_time_rows`` —
    one shared sort of the real table serves every pending reservation)
    confirms the promised nodes, and the job starts exactly at its
    reserved time.  Uncapped, realized == reserved always (asserted by
    the mirror's ``check_reservations``); under a binding power cap a
    deferred start breaks promises downstream, and realized starts
    degrade gracefully to ``max(reserved, realizable, power-feasible)``
    in reservation order.  The ``window`` bounds the reservation horizon
    (pending slots); admission stalls when it is full.

    Compared to EASY this queue both *guards more* (every reservation,
    not just the head's) and *backfills more*: EASY only exploits the
    idle gap under the head's reservation (everything else is committed
    eagerly), while the interval table exposes the holes under EVERY
    pending job.  Faults ride the event stream as in
    ``make_event_step``: with ``retries`` a failing first attempt
    occupies exactly its reserved span (the failure IS a completion
    event) and re-queues for a fresh reservation at the failure time.

    Factored form: as ``make_event_step`` — returns the bare
    ``step(ctx, carry, horizon)`` shared verbatim by the batch scan
    (``_sim_pieces``, open horizon) and the service dispatcher
    (finite horizon gates the clock and the stuck valve).
    """
    Wc = int(policy.window) + 1
    tiered = policy.tiered
    idx = jnp.arange(Wc)

    def step(ctx, carry, horizon):
        arrs, fvec = ctx["arrs"], ctx["fvec"]
        sel_key, fault_key = ctx["sel_key"], ctx["fault_key"]
        tt = ctx["tt"] if tiered else None
        T_true, C_true, E_true = (arrs["T_true"], arrs["C_true"],
                                  arrs["E_true"])
        T_pred, C_pred = arrs["T_pred"], arrs["C_pred"]
        n_req, prog, arrival = arrs["n_req"], arrs["prog"], arrs["arrival"]
        outage = arrs.get("outage")
        w_pow, idle_w = arrs["w_pow"], arrs["idle_w"]
        # per-job effective K at use (see make_event_step's k_of)
        pol_k = jnp.asarray(policy.k, jnp.float32)
        k_of = lambda j: jnp.where(jnp.isnan(arrs["k_job"][j]), pol_k,
                                   arrs["k_job"][j])
        S = T_true.shape[1]
        J = prog.shape[0]
        with _stage("select"):
            exists = arrs["free0"] < BIG
            idle_mat = jnp.where(exists, idle_w[:, None], 0.0)
            pc = jnp.asarray(policy.power_cap, jnp.float32)
            capped = pc < UNCAPPED
        with _stage("advance"):
            out_ends = (None if outage is None
                        else outage[..., 1].reshape(-1))
        #: per-slot pop fill values (sentinel slot state)
        FILLS = dict(pend=J, t0=0.0, rt=False, accT=0.0, accF=0.0,
                     accW=0.0, s0=0.0, pblock=BIG, sel=0, start=0.0,
                     fin=0.0, T=1.0, E=0.0, need=0, wjob=0.0, fac=0.0,
                     fail=False, tier=0)
        sys_col = jnp.arange(S)[:, None, None]                   # [S, 1, 1]

        def earliest_fit(jp, p, t0, Tdur, node_free, slots):
            """Per-system earliest start where free capacity (really-free
            node count minus reservation occupancy) covers job ``jp``'s
            nodes (``_job_need``) for the whole [t, t + Tdur) window.
            Candidates: the arrival floor, node free times, reservation
            finishes (the only capacity rises); dips happen only at
            reservation starts, so each candidate is checked against the
            [W] reservation table."""
            with _stage("earliest"):
                need = _job_need(arrs, jp, p)                        # [S]
                r_valid = slots["pend"] < J                          # [Wc]
                r_sel, r_sta = slots["sel"], slots["start"]
                r_fin, r_need = slots["fin"], slots["need"]
                cands = jnp.concatenate([
                    jnp.full((S, 1), t0, jnp.float32), node_free,
                    jnp.broadcast_to(r_fin[None], (S, Wc)),
                ], axis=1)                                           # [S, E]
                cands = jnp.maximum(cands, t0)
                if outage is not None:
                    # start gating only (jobs ride through windows, as in
                    # the other cores); outage ends are free-time
                    # candidates via the floored duplicates below
                    for wi in range(outage.shape[1]):
                        o0 = outage[:, wi, 0][:, None]
                        o1 = outage[:, wi, 1][:, None]
                        cands = jnp.where((cands >= o0) & (cands < o1), o1,
                                          cands)
                q = jnp.concatenate(
                    [cands, jnp.broadcast_to(r_sta[None], (S, Wc))], axis=1)
                cnt = jnp.sum(node_free[:, None, :] <= q[:, :, None], axis=2)
                on_sys = (r_valid[None, None, :]
                          & (r_sel[None, None, :] == sys_col))
                occ = jnp.sum(jnp.where(
                    on_sys & (r_sta[None, None, :] <= q[:, :, None])
                    & (q[:, :, None] < r_fin[None, None, :]),
                    r_need[None, None, :], 0), axis=2)
                availn = cnt - occ                               # [S, E + Wc]
                E_c = cands.shape[1]
                cap_ok = availn[:, :E_c] >= need[:, None]            # [S, E]
                avail_rs = availn[:, E_c:]                           # [S, Wc]
                dips = (on_sys & (cands[:, :, None] < r_sta[None, None, :])
                        & (r_sta[None, None, :]
                           < cands[:, :, None] + Tdur[:, None, None]))
                dip_ok = jnp.all(
                    ~dips | (avail_rs[:, None, :] >= need[:, None, None]),
                    axis=2)
                return jnp.min(jnp.where(cap_ok & dip_ok, cands, BIG),
                               axis=1)

        def reserve(jp, t0, is_retry, node_free, slots, C_tab, T_tab, runs):
            """Admission: fault draw + hole-aware earliest fit + selection —
            the new reservation row for the slot table."""
            with _stage("earliest"):
                p = prog[jp]
            with _stage("fault"):
                u = jax.random.uniform(jax.random.fold_in(fault_key, jp),
                                       (2,))
                slow = jnp.where(u[0] < fvec[0], fvec[1], 1.0)
                fail = u[1] < fvec[2]
                if retries:
                    first_fail = fail & ~is_retry
                    scale = jnp.where(first_fail, fvec[3], 1.0)
                else:
                    first_fail = jnp.zeros((), bool)
                    scale = jnp.where(fail, 1.0 + fvec[3], 1.0)
                factor = slow * scale
            with _stage("select"):
                key = jax.random.fold_in(sel_key, jp)
            # this job's own runtime (and width) where the workload has
            # per-job columns: reservations span the realised runtime, as
            # they do for a fault-scaled one
            ts = _job_t_scale(arrs, jp)
            if ts is not None:
                with _stage("job"):
                    factor_t = factor * ts
            else:
                factor_t = factor
            if tiered:
                # hole-aware earliest fit per tier: a slower tier's longer
                # window may fit a different hole, so each tier gets its
                # own piecewise-capacity evaluation
                with _stage("fault"):
                    Tdur_f = tt["T"][p] * factor_t               # [F, S]
                avail_f = jax.vmap(
                    lambda td: earliest_fit(jp, p, t0, td, node_free, slots)
                )(Tdur_f)                                        # [F, S]
                with _stage("select"):
                    c_x, t_x, runs_x, avail_x, cp_x, tp_x = _tier_rows(
                        tt, p, C_tab[p], T_tab[p], runs[p], avail_f,
                        C_pred[p], T_pred[p])
                    sel_x = select(
                        policy, c_row=c_x, t_row=t_x, runs_row=runs_x,
                        avail_row=avail_x, k=k_of(jp), c_pred_row=cp_x,
                        t_pred_row=tp_x, key=key)
                    f = (sel_x // S).astype(jnp.int32)
                    sel = sel_x % S
                with _stage("push"):
                    start = avail_f[f, sel]
                    T_act = Tdur_f[f, sel]
                    E_res = tt["E"][p, f, sel] * factor
                    wjob = tt["w"][p, f, sel]
            else:
                with _stage("fault"):
                    Tdur = T_true[p] * factor_t                      # [S]
                avail_p = earliest_fit(jp, p, t0, Tdur, node_free, slots)
                with _stage("select"):
                    sel = select(
                        policy, c_row=C_tab[p], t_row=T_tab[p],
                        runs_row=runs[p], avail_row=avail_p, k=k_of(jp),
                        c_pred_row=C_pred[p], t_pred_row=T_pred[p],
                        key=key)
                with _stage("push"):
                    f = jnp.int32(0)
                    start = avail_p[sel]
                    T_act = Tdur[sel]
                    E_res = E_true[p, sel] * factor
                    wjob = w_pow[p, sel]
            scales = _job_scales(arrs, jp, p, sel)
            if scales is not None:
                with _stage("job"):
                    E_res, wjob = E_res * scales[1], wjob * scales[2]
            with _stage("push"):
                return dict(sel=sel.astype(jnp.int32), start=start,
                            fin=start + T_act, T=T_act,
                            E=E_res, need=_job_need(arrs, jp, p, sel),
                            wjob=wjob, fac=factor, fail=first_fail, tier=f)

        (node_free, node_pow, C_tab, T_tab, runs, acc, busy,
         slots, a, now, nbf, peak, cdel) = carry

        # ---- push: admit + reserve the next arrival if due and room
        with _stage("push"):
            size0 = jnp.sum(slots["pend"] < J)
            jp = jnp.minimum(a, J - 1)
            arr_a = arrival[jp]
            do_push = (a < J) & (size0 < Wc) & (arr_a <= now)
            vals = reserve(jp, arr_a, jnp.zeros((), bool), node_free, slots,
                           C_tab, T_tab, runs)
            slot = jnp.minimum(size0, Wc - 1)
            newv = dict(pend=jp.astype(jnp.int32), t0=arr_a, rt=False,
                        accT=0.0, accF=0.0, accW=0.0, s0=0.0, pblock=BIG,
                        **vals)
            slots = {k: v.at[slot].set(jnp.where(do_push, newv[k], v[slot]))
                     for k, v in slots.items()}
            a = a + do_push

        with _stage("earliest"):
            valid = slots["pend"] < J
            r_start, r_sel = slots["start"], slots["sel"]
            r_need = slots["need"]

        # ---- next event: arrivals, completions, reservation starts,
        # outage ends (reserved starts need not coincide with node-free
        # times once a cap defers placements)
        with _stage("advance"):
            next_evt = jnp.min(jnp.where(node_free > now, node_free, BIG))
            arr_next = arrival[jnp.minimum(a, J - 1)]
            next_evt = jnp.minimum(
                next_evt,
                jnp.where((a < J) & (arr_next > now), arr_next, BIG))
            next_evt = jnp.minimum(
                next_evt,
                jnp.min(jnp.where(valid & (r_start > now), r_start, BIG)))
            if out_ends is not None:
                next_evt = jnp.minimum(
                    next_evt,
                    jnp.min(jnp.where(out_ends > now, out_ends, BIG)))

        # ---- realizability on the REAL table (one shared sort)
        with _stage("earliest"):
            kth_rows = kth_free_time_rows(node_free, r_sel, r_need,
                                          force=placer)          # [Wc]
            avail_real = jnp.maximum(jnp.where(valid, slots["t0"], BIG),
                                     kth_rows)
            if outage is not None:
                avail_real = _push_out_of_outage(avail_real, outage[r_sel])
        with _stage("select"):
            elig_res = valid & (r_start <= now) & (avail_real <= now)
            if outage is not None:
                # cap-deferred starts quantize to ``now``: the start gate
                # must hold there too (reserved starts are already pushed)
                q = jnp.maximum(r_start, now)
                gated = _push_out_of_outage(q, outage[r_sel])
                elig_res = elig_res & (~capped | (gated <= now))

            # ---- power feasibility + the stuck valve
            p_now = jnp.sum(jnp.where(node_free > now, node_pow, idle_mat))
            new_P = p_now - r_need * idle_w[r_sel] + slots["wjob"]
            power_ok = ~capped | (new_P <= pc)
            elig0 = elig_res & power_ok
            stuck = (jnp.any(elig_res) & ~do_push & ~jnp.any(elig0)
                     & (next_evt >= BIG) & (horizon >= BIG))
            elig = elig0 | (elig_res & stuck)

            chosen = jnp.min(jnp.where(elig, idx, Wc))
            placed = chosen < Wc
            ci = jnp.minimum(chosen, Wc - 1)

            chosen_res = jnp.min(jnp.where(elig_res, idx, Wc))
            cri = jnp.minimum(chosen_res, Wc - 1)
            blocked = (chosen_res < Wc) & ~power_ok[cri]
            slots["pblock"] = slots["pblock"].at[cri].set(
                jnp.where(blocked, jnp.minimum(slots["pblock"][cri], now),
                          slots["pblock"][cri]))

        # ---- realize the chosen reservation
        with _stage("alloc"):
            jj = jnp.minimum(slots["pend"][ci], J - 1)
            p = prog[jj]
            sel, need = r_sel[ci], jnp.maximum(r_need[ci], 1)
            T_act, E_act = slots["T"][ci], slots["E"][ci]
            fac = slots["fac"][ci]
            tier_ci = slots["tier"][ci]
            start = jnp.where(capped, jnp.maximum(r_start[ci], now),
                              r_start[ci])
            finish = start + T_act
            failed_now = placed & slots["fail"][ci]
            final = placed & ~slots["fail"][ci]
            accT_ci, accF_ci = slots["accT"][ci], slots["accF"][ci]
            accW_ci = slots["accW"][ci]
            s0_ci = jnp.where(slots["rt"][ci], slots["s0"][ci], start)
            wait_step = start - slots["t0"][ci]
            pb_ci = slots["pblock"][ci]

            kth_ci = kth_rows[ci]
            take = _alloc_mask(node_free, sel, kth_ci, need)
            node_free = jnp.where(
                placed, _alloc(node_free, sel, kth_ci, need, finish),
                node_free)
            per_node = slots["wjob"][ci] / need.astype(jnp.float32)
            node_pow = jnp.where(
                placed,
                node_pow.at[sel].set(jnp.where(take, per_node,
                                               node_pow[sel])),
                node_pow)

        with _stage("learn"):
            fac_tot = accF_ci + fac
            C_upd = C_true[p, sel] * fac_tot
            T_upd = T_true[p, sel] * fac_tot
        scales = _job_scales(arrs, jj, p, sel)
        if scales is not None:
            with _stage("job"):
                T_upd = T_upd * scales[0]
        with _stage("learn"):
            n = runs[p, sel].astype(jnp.float32)
            C_tab = C_tab.at[p, sel].set(jnp.where(
                final, _running_mean(C_tab[p, sel], n, C_upd, scales),
                C_tab[p, sel]))
            T_tab = T_tab.at[p, sel].set(jnp.where(
                final, _running_mean(T_tab[p, sel], n, T_upd, scales),
                T_tab[p, sel]))
            runs = runs.at[p, sel].add(jnp.where(final, 1, 0))

        with _stage("account"):
            busy = busy.at[sel].add(jnp.where(placed, T_act * need, 0.0))
            nbf = nbf + (final & (chosen > 0)).astype(jnp.int32)
            peak = jnp.maximum(peak, jnp.where(placed, new_P[ci], 0.0))
            cdel = cdel + jnp.where(placed & (pb_ci < BIG), now - pb_ci,
                                    0.0)

        with _stage("push"):
            def pop(arr, fill):
                shifted = jnp.concatenate(
                    [arr[1:], jnp.full((1,), fill, arr.dtype)])
                return jnp.where(idx < chosen, arr, shifted)
            slots = {k: pop(v, FILLS[k]) for k, v in slots.items()}

            if retries:
                # failed first attempt: fresh reservation at the failure
                # time
                vals2 = reserve(jj, finish, jnp.ones((), bool), node_free,
                                slots, C_tab, T_tab, runs)
                size2 = jnp.sum(slots["pend"] < J)
                slot2 = jnp.minimum(size2, Wc - 1)
                newv2 = dict(pend=jj.astype(jnp.int32), t0=finish, rt=True,
                             accT=accT_ci + T_act, accF=fac_tot,
                             accW=accW_ci + wait_step, s0=s0_ci, pblock=BIG,
                             **vals2)
                slots = {k: v.at[slot2].set(
                    jnp.where(failed_now, newv2[k], v[slot2]))
                    for k, v in slots.items()}

        with _stage("account"):
            T_tot = accT_ci + T_act
            wait_tot = accW_ci + wait_step

        # ---- advance the clock only when nothing else happened (and
        # never past the horizon)
        with _stage("advance"):
            advance = (~do_push & ~placed & (next_evt < BIG)
                       & (next_evt <= horizon))
            now = jnp.where(advance, next_evt, now)

        with _stage("account"):
            if totals_only:
                sums, comps, fin_max, wait_max = acc
                add = jnp.stack([
                    E_act,
                    jnp.where(final, wait_tot, 0.0),
                    jnp.where(final, (wait_tot + T_tot) / T_tot, 0.0)])
                y = add - comps
                t = sums + y
                acc = (jnp.where(placed, t, sums),
                       jnp.where(placed, (t - sums) - y, comps),
                       jnp.maximum(fin_max, jnp.where(placed, finish, 0.0)),
                       jnp.maximum(wait_max, jnp.where(final, wait_tot,
                                                       0.0)))
                out = None
            else:
                out = {
                    # batch-result channels (_event_results scatters these)
                    "j_add": jnp.where(placed, jj, J), "E": E_act,
                    "j_fin": jnp.where(final, jj, J), "sys": sel,
                    "s0": s0_ci, "finish": finish, "wait": wait_tot,
                    "T": T_tot, "bf": final & (chosen > 0),
                    "tier": tier_ci,
                    # live-decision channels (the service dispatcher reads
                    # these; pure additions, the batch channels are
                    # untouched)
                    "pushed": do_push,
                    "j_push": jnp.where(do_push, a - 1, J),
                    "placed": placed, "final": final, "advanced": advance,
                    "start": start, "now": now,
                    "qlen": jnp.sum(slots["pend"] < J),
                    "power": jnp.where(placed, new_P[ci], p_now),
                }

        return ConsCarry(node_free, node_pow, C_tab, T_tab, runs, acc,
                         busy, slots, a, now, nbf, peak, cdel), out

    return step


@partial(jax.jit, static_argnames=("warm_start", "placer", "totals_only",
                                   "easy_eval", "core", "retries"))
def _batched_run(arrs, policy, seeds, faults, *, warm_start, placer,
                 totals_only, easy_eval="batched", core="arrival",
                 retries=False):
    """vmap the scan core over a flat batch axis: policy leaves [B], seeds
    [B], faults [B, 4].  One compile per (shapes, policy metadata,
    warm_start, placer, totals_only, easy_eval, core, retries)."""
    return jax.vmap(
        lambda pol, sd, fv: _scan_sim(arrs, pol, warm_start, placer,
                                      totals_only, sd, fv, easy_eval,
                                      core, retries))(
        policy, seeds, faults)


#: static argnames shared by the sharded/chunked grid entries; ``mesh``
#: (a hashable jax.sharding.Mesh, or None = single-device) is static so
#: shard_map specializes per mesh like every other compile key
_GRID_STATICS = ("warm_start", "placer", "totals_only", "easy_eval",
                 "core", "retries", "mesh")


@partial(jax.jit, static_argnames=_GRID_STATICS)
def _sharded_run(arrs, policy, seeds, faults, *, mesh, warm_start, placer,
                 totals_only, easy_eval="batched", core="arrival",
                 retries=False):
    """``_batched_run`` with the flat batch axis partitioned over a 1-D
    ``("grid",)`` mesh (launch.mesh.make_grid_mesh): each device vmaps
    its B/n slice of the (policy leaves, seeds, faults) batch against
    the replicated workload arrays.  Grid lanes never communicate, so
    sharding is a pure partition of the batch axis and results are
    bit-identical to the single-device vmap (asserted in
    tests/test_sharded_campaign.py)."""
    def body(arrs_, pol, sd, fv):
        return jax.vmap(
            lambda p_, s_, f_: _scan_sim(arrs_, p_, warm_start, placer,
                                         totals_only, s_, f_, easy_eval,
                                         core, retries))(pol, sd, fv)
    return jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(_replicated, _grid_spec, _grid_spec, _grid_spec),
        out_specs=_grid_spec)(arrs, policy, seeds, faults)


@partial(jax.jit, static_argnames=_GRID_STATICS)
def _chunk_init(arrs, policy, seeds, faults, *, mesh, warm_start, placer,
                totals_only, easy_eval="batched", core="arrival",
                retries=False):
    """Initial [B]-leading carries of the chunked campaign (sharded over
    ``mesh`` when given, so the carry is born device-resident on its
    shard and never gathers)."""
    def body(arrs_, pol, sd, fv):
        return jax.vmap(
            lambda p_, s_, f_: _sim_pieces(
                arrs_, p_, warm_start, placer, totals_only, s_, f_,
                easy_eval, core, retries).carry0)(pol, sd, fv)
    if mesh is None:
        return body(arrs, policy, seeds, faults)
    return jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(_replicated, _grid_spec, _grid_spec, _grid_spec),
        out_specs=_grid_spec)(arrs, policy, seeds, faults)


@partial(jax.jit, static_argnames=_GRID_STATICS + ("nsteps",))
def _chunk_advance(arrs, policy, seeds, faults, carries, xs, *, mesh,
                   nsteps, warm_start, placer, totals_only,
                   easy_eval="batched", core="arrival", retries=False):
    """Advance every batch lane ``nsteps`` scan steps: the per-lane step
    closure is the monolithic scan's own (``_sim_pieces``), the carry is
    threaded in and out, and ``xs`` is the host-sliced window of the
    stream inputs (replicated across shards; None for the event cores,
    whose scans are length-driven).  At most two compilations exist per
    configuration: the full chunk and the remainder."""
    def body(arrs_, pol, sd, fv, carry, xs_):
        def lane(p_, s_, f_, c_):
            pieces = _sim_pieces(arrs_, p_, warm_start, placer,
                                 totals_only, s_, f_, easy_eval, core,
                                 retries)
            return jax.lax.scan(pieces.step, c_, xs_, length=nsteps)
        return jax.vmap(lane)(pol, sd, fv, carry)
    if mesh is None:
        return body(arrs, policy, seeds, faults, carries, xs)
    return jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(_replicated, _grid_spec, _grid_spec, _grid_spec,
                  _grid_spec, _replicated),
        out_specs=_grid_spec)(arrs, policy, seeds, faults, carries, xs)


@partial(jax.jit, static_argnames=_GRID_STATICS)
def _chunk_finish(arrs, policy, seeds, faults, carries, ys, *, mesh,
                  warm_start, placer, totals_only, easy_eval="batched",
                  core="arrival", retries=False):
    """The routed core's result epilogue over final carries (+ the
    reassembled per-step outputs on the full path; None when
    ``totals_only``) — the same ops the monolithic scan's finish runs."""
    def body(arrs_, pol, sd, fv, carry, ys_):
        return jax.vmap(
            lambda p_, s_, f_, c_, y_: _sim_pieces(
                arrs_, p_, warm_start, placer, totals_only, s_, f_,
                easy_eval, core, retries).finish(c_, y_))(
            pol, sd, fv, carry, ys_)
    if mesh is None:
        return body(arrs, policy, seeds, faults, carries, ys)
    return jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(_replicated, _grid_spec, _grid_spec, _grid_spec,
                  _grid_spec, _grid_spec),
        out_specs=_grid_spec)(arrs, policy, seeds, faults, carries, ys)


def _run_chunked(arrs, policy, seeds, faults, *, chunk, mesh, warm_start,
                 placer, totals_only, easy_eval="batched", core="arrival",
                 retries=False):
    """Stream the campaign scan through fixed-size windows of ``chunk``
    steps: jitted per-chunk advances thread the carry, per-step outputs
    (full path only) spill to host per chunk and are reassembled for the
    shared finish.  The step trace is the monolithic scan's own, so
    results are bit-identical (asserted per core in
    tests/test_sharded_campaign.py).  ``totals_only`` keeps O(B) carry
    state end to end — no [B, J]-shaped intermediate ever materializes,
    which is what lets a 10^6-job trace stream through device memory."""
    kw = dict(mesh=mesh, warm_start=warm_start, placer=placer,
              totals_only=totals_only, easy_eval=easy_eval, core=core,
              retries=retries)
    xs, length = _stream_xs(arrs, policy, core, retries)
    chunk = max(1, int(chunk))
    args = (arrs, policy, seeds, faults)
    carries = _chunk_init(*args, **kw)
    programs = [(_chunk_init, kw, args)]
    parts = []
    for lo in range(0, length, chunk):
        n = min(chunk, length - lo)
        xs_c = (None if xs is None
                else jax.tree.map(lambda x: x[lo:lo + n], xs))
        if lo == 0 or n != chunk:          # the full chunk, the remainder
            programs.append((_chunk_advance, {**kw, "nsteps": n},
                             (*args, carries, xs_c)))
        carries, ys = _chunk_advance(*args, carries, xs_c, nsteps=n, **kw)
        if not totals_only:
            parts.append(jax.device_get(ys))
    ys_all = None
    if not totals_only:
        ys_all = jax.tree.map(lambda *cs: np.concatenate(cs, axis=1),
                              *parts)
    out = _chunk_finish(*args, carries, ys_all, **kw)
    _record_programs(programs + [(_chunk_finish, kw,
                                  (*args, carries, ys_all))])
    return out


def _fault_vec(cfg: SimConfig | FaultConfig):
    return jnp.array([cfg.straggler_prob, cfg.straggler_factor,
                      cfg.failure_prob, cfg.restart_overhead], jnp.float32)


#: distinguishes "core= not passed" from an explicit core=None (both mean
#: auto granularity, but only the explicit spelling earns the deprecation
#: warning)
_CORE_UNSET = object()


def stack_sessions(trees):
    """Stack N same-structure session pytrees (carries / contexts /
    scalar-leaf policies) along a new leading axis — the pool's [N, ...]
    batch the vmapped step consumes.  Leaves must agree in shape, which
    the fixed-capacity session arrays guarantee."""
    trees = list(trees)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def index_session(tree, i: int):
    """Slice session ``i`` back out of a stacked pool pytree (the inverse
    of ``stack_sessions`` for one lane)."""
    return jax.tree.map(lambda x: x[i], tree)


class Scheduler:
    """The one entry point: a policy (point or grid), a placement backend,
    optional fault and seed grids — ``run`` simulates everything in a
    single jitted call.

    policy:     registered name, or a ``Policy`` (leaf-batch ``k`` /
                ``ucb_scale`` with a shared leading axis to sweep a
                hyperparameter grid in one compilation)
    placer:     kth-free dispatch (None = auto; "pallas" / "jnp" / "sort" /
                "pallas_interpret")
    faults:     one FaultConfig (no axis) or an iterable (adds a ``fault``
                axis); None = fault-free
    seeds:      one int (no axis) or an iterable (adds a ``seed`` axis)
    warm_start: profile tables pre-filled with ground truth
    queue:      queue-discipline spec overriding the policy's metadata:
                "fcfs" | "easy_backfill[:window=W]" |
                "conservative[:window=W]" (None = keep the policy's own)
    easy_eval:  EASY candidate-evaluation strategy (static): "batched"
                (default — one [W, S] kth-free call per step) or
                "unrolled" (the historical per-slot loop, kept as the
                bit-identity reference; ~W x slower at large windows)
    power_cap:  SCC power cap in Watts — a scalar, or a 1-D grid that
                leaf-batches with k/ucb_scale (cap sweeps share one jit).
                Overrides the policy's ``power_cap`` leaf; any finite cap
                routes onto the event-granular core.  None = keep the
                policy's leaf (default: uncapped).
    engine:     scan granularity: None (auto — "events" for conservative
                queues or finite power caps, "arrival" otherwise),
                "arrival" (the historical arrival-indexed scans), or
                "events" (force the event-granular core the online
                dispatcher runs — see docs/SERVICE.md; FCFS placements
                are bit-identical to "arrival", asserted per registered
                policy in tests/test_event_core.py; EASY divergence vs
                the arrival-indexed scan is documented in
                tests/test_service.py).
    core:       DEPRECATED spelling of ``engine`` (emits a
                ``DeprecationWarning``; docs/API.md migration table).
                Passing both with different values is an error.
    shards:     partition the flat (fault x policy x seed) batch axis
                over the local devices via shard_map on a 1-D
                ``("grid",)`` mesh: "auto" = every local device, or an
                explicit count; None (default) = single-device vmap.
                Lanes never communicate, so sharded results are
                bit-identical to unsharded.  The batch is padded to a
                multiple of the device count (duplicate tail lanes,
                sliced off the result).
    chunk:      stream the scan in windows of ``chunk`` steps instead of
                one monolithic lax.scan: the carry threads between
                jitted per-chunk advances, per-job outputs spill to host
                per chunk (full path), and ``totals_only`` stays O(grid)
                memory with no [grid, J] intermediate ever materialized
                — the million-job campaign mode.  Bit-identical to the
                monolithic scan (same step trace).  None (default) =
                monolithic.  Composes with ``shards``.

    ``run(w)`` returns a ``SimResult`` when no axis is present, else a
    ``CampaignResult`` with ``axes`` ordered (fault, policy, seed) — the
    legacy campaign layout.  ``totals_only=True`` skips materializing
    per-job arrays (campaign memory: [*grid] aggregates instead of
    [*grid, J]).
    """

    def __init__(self, policy: str | Policy = "paper", *,
                 placer: str | None = None, faults=None, seeds=0,
                 warm_start: bool = False, queue: str | None = None,
                 easy_eval: str = "batched", power_cap=None,
                 engine: str | None = None, core=_CORE_UNSET,
                 shards=None, chunk=None):
        if core is not _CORE_UNSET:
            warnings.warn(
                "Scheduler(core=...) is deprecated; use engine=... "
                "(docs/API.md migration table)", DeprecationWarning,
                stacklevel=2)
            if engine is not None and core is not None and core != engine:
                raise ValueError(f"core={core!r} conflicts with "
                                 f"engine={engine!r}")
            if engine is None:
                engine = core
        self.policy = make_policy(policy) if isinstance(policy, str) else policy
        if queue is not None:
            self.policy = apply_queue_spec(self.policy, queue)
        if power_cap is not None:
            self.policy = replace(self.policy,
                                  power_cap=np.asarray(power_cap, np.float32))
        if easy_eval not in ("batched", "unrolled"):
            raise ValueError(f"easy_eval {easy_eval!r} not in "
                             "('batched', 'unrolled')")
        if engine not in (None, "arrival", "events"):
            raise ValueError(f"engine {engine!r} not in (None, 'arrival', "
                             "'events')")
        if engine == "arrival" and self.policy.queue == "conservative":
            raise ValueError("queue='conservative' requires the event-"
                             "granular core (engine='events' or None)")
        if engine == "arrival" and self.policy.capped:
            raise ValueError("a finite power_cap requires the event-"
                             "granular core (engine='events' or None): the "
                             "arrival-indexed scan cannot defer placements")
        if shards is not None and shards != "auto":
            shards = int(shards)
            if shards < 1:
                raise ValueError(f"shards must be >= 1 or 'auto', "
                                 f"got {shards}")
        self.shards = shards
        if chunk is not None:
            chunk = int(chunk)
            if chunk < 1:
                raise ValueError(f"chunk must be a positive step count, "
                                 f"got {chunk}")
        self.chunk = chunk
        self.engine = engine
        self.easy_eval = easy_eval
        self.placer = placer
        self.warm_start = bool(warm_start)
        if faults is None or isinstance(faults, FaultConfig):
            self.faults = faults
        else:
            self.faults = tuple(faults)
        self.seeds = seeds if isinstance(seeds, (int, np.integer)) \
            else tuple(int(s) for s in seeds)

    @property
    def core(self):
        """Deprecated read alias of ``engine`` (docs/API.md migration)."""
        return self.engine

    def _grid(self, w: Workload, totals_only: bool):
        """The flat-batch inputs of one ``run``: workload arrays, the
        leaf-batched policy, seeds and fault rows (padded to the mesh),
        the static compile keys, and the axis bookkeeping ``run`` needs
        to reshape and label the result."""
        pol = self.policy
        k = jnp.asarray(pol.k, jnp.float32)
        u = jnp.asarray(pol.ucb_scale, jnp.float32)
        pc = jnp.asarray(pol.power_cap, jnp.float32)
        fw = jnp.asarray(pol.freq_weight, jnp.float32)
        if k.ndim > 1 or u.ndim > 1 or pc.ndim > 1 or fw.ndim > 1:
            raise ValueError("policy leaves must be scalars or 1-D grids; "
                             "flatten K x ucb meshes with .ravel()")
        has_policy_axis = (k.ndim == 1 or u.ndim == 1 or pc.ndim == 1
                           or fw.ndim == 1)
        k, u, pc, fw = jnp.broadcast_arrays(
            jnp.atleast_1d(k), jnp.atleast_1d(u), jnp.atleast_1d(pc),
            jnp.atleast_1d(fw))
        G = k.shape[0]

        has_seed_axis = not isinstance(self.seeds, (int, np.integer))
        seeds = jnp.atleast_1d(jnp.asarray(self.seeds, jnp.int32))
        R = seeds.shape[0]

        has_fault_axis = isinstance(self.faults, tuple)
        if self.faults is None:
            fmat = _fault_vec(FaultConfig())[None]
        elif has_fault_axis:
            fmat = jnp.stack([_fault_vec(f) for f in self.faults])
        else:
            fmat = _fault_vec(self.faults)[None]
        F = fmat.shape[0]

        # core routing (static): conservative queues and finite caps need
        # completion-event granularity; mid-job failure re-queue rides the
        # event stream whenever the fault grid can fail jobs
        core = self.engine or ("events" if (pol.queue == "conservative"
                                            or pol.capped) else "arrival")
        fault_list = (() if self.faults is None else
                      (self.faults,) if isinstance(self.faults, FaultConfig)
                      else self.faults)
        retries = core == "events" and any(
            f.failure_prob > 0 for f in fault_list)

        B = F * G * R
        kb = jnp.broadcast_to(k[None, :, None], (F, G, R)).reshape(B)
        ub = jnp.broadcast_to(u[None, :, None], (F, G, R)).reshape(B)
        pb = jnp.broadcast_to(pc[None, :, None], (F, G, R)).reshape(B)
        fwb = jnp.broadcast_to(fw[None, :, None], (F, G, R)).reshape(B)
        sb = jnp.broadcast_to(seeds[None, None, :], (F, G, R)).reshape(B)
        fb = jnp.broadcast_to(fmat[:, None, None, :], (F, G, R, 4))
        fbB = fb.reshape(B, 4)

        mesh, pad = None, 0
        if self.shards is not None:
            # lazy: core must stay importable without touching device
            # state (launch.mesh counts devices at call time only)
            from repro.launch.mesh import make_grid_mesh
            mesh = make_grid_mesh(self.shards)
            pad = (-B) % mesh.devices.size
            if pad:
                # shard_map needs B % n_devices == 0: duplicate the last
                # lane (cheapest valid work) and slice it back off below
                def padb(x):
                    tail = jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])
                    return jnp.concatenate([x, tail])
                kb, ub, pb, fwb, sb, fbB = map(
                    padb, (kb, ub, pb, fwb, sb, fbB))

        polb = replace(pol, k=kb, ucb_scale=ub, power_cap=pb,
                       freq_weight=fwb)
        common = dict(warm_start=self.warm_start, placer=self.placer,
                      totals_only=totals_only, easy_eval=self.easy_eval,
                      core=core, retries=retries)
        axes, lead = [], []
        for name, present, size in (("fault", has_fault_axis, F),
                                    ("policy", has_policy_axis, G),
                                    ("seed", has_seed_axis, R)):
            if present:
                axes.append(name)
                lead.append(size)
        return dict(args=(_workload_arrays(w), polb, sb, fbB), mesh=mesh,
                    common=common, B=B, pad=pad, axes=tuple(axes),
                    lead=tuple(lead),
                    policy_coord=replace(pol, k=k, ucb_scale=u, power_cap=pc,
                                         freq_weight=fw))

    def lower(self, w: Workload, *, totals_only: bool = False):
        """The ``jax.stages.Lowered`` program that runs this
        configuration's scan over ``w``: the per-chunk advance at full
        chunk length when ``chunk`` is set, else the batched (or sharded)
        run.  ``.compile().as_text()`` shows what the backend made of it
        — e.g. whether the Pallas placement kernel (``tpu_custom_call``)
        is in the compiled step."""
        g = self._grid(w, totals_only)
        args, mesh, common = g["args"], g["mesh"], g["common"]
        if self.chunk is None:
            if mesh is not None:
                return _sharded_run.lower(*args, mesh=mesh, **common)
            return _batched_run.lower(*args, **common)
        xs, length = _stream_xs(args[0], args[1], common["core"],
                                common["retries"])
        n = min(self.chunk, length)
        carries = jax.eval_shape(
            partial(_chunk_init, mesh=mesh, **common), *args)
        xs_c = None if xs is None else jax.tree.map(lambda x: x[:n], xs)
        return _chunk_advance.lower(*args, carries, xs_c, mesh=mesh,
                                    nsteps=n, **common)

    def run(self, w: Workload, *, totals_only: bool = False):
        g = self._grid(w, totals_only)
        args, mesh, common = g["args"], g["mesh"], g["common"]
        if self.chunk is not None:
            out = _run_chunked(*args, chunk=self.chunk, mesh=mesh, **common)
        elif mesh is not None:
            out = _sharded_run(*args, mesh=mesh, **common)
            _record_programs([(_sharded_run, {"mesh": mesh, **common},
                               args)])
        else:
            out = _batched_run(*args, **common)
            _record_programs([(_batched_run, common, args)])
        if g["pad"]:
            out = jax.tree.map(lambda x: x[:g["B"]], out)

        pol, axes, lead = self.policy, g["axes"], g["lead"]
        out = jax.tree.map(lambda x: x.reshape(lead + x.shape[1:]), out)

        meta = dict(axes=axes, n_jobs=int(len(w.prog)),
                    n_nodes=np.asarray(w.n_nodes), programs=w.programs,
                    systems=w.systems, freq_tiers=pol.freq_tiers)
        if not axes:
            return SimResult(**out, **meta)
        coords = {}
        if "fault" in axes:
            coords["fault"] = self.faults
        if "policy" in axes:
            coords["policy"] = g["policy_coord"]
        if "seed" in axes:
            coords["seed"] = self.seeds
        return CampaignResult(**out, **meta, coords=coords)
