"""Beyond-paper scheduler ablation on campaign-scale scenario streams.

Every registered policy on a bursty mixed-class job stream via the
``Scheduler`` facade (each policy's whole K x seed grid is ONE jitted
call), reporting the energy / makespan / wait Pareto — the paper's
algorithm is the tunable middle; predictive cold-start removes exploration
waste (DESIGN.md §9).  The fault-tolerance sweep drives the same stream
through a FaultConfig grid in a single call.

``run_policy_grid`` is the hyperparameter-grid demonstration: because
Policy hyperparameters (K, ucb_scale) are PyTree leaves, a 32-point
K x ucb-scale mesh is ONE leaf-batched Policy — a single jitted
``Scheduler.run`` vmaps the whole grid without re-tracing per point
(asserted on the jit cache).

``run_queue_disciplines`` is the queue-discipline ablation (ISSUE 3):
FCFS vs EASY backfilling on the contended SWF-replay and diurnal streams
the classic HPC literature evaluates with backfill; EASY must strictly
improve mean wait on at least one of them (asserted).

``run_window_scaling`` is the batched-candidate-evaluation proof
(ISSUE 4): the EASY warm wall-clock across W in {4, 8, 16, 32} with the
FCFS baseline, asserted >= 5x faster than the PR 3 unrolled loop's
committed W=16 row (machine-speed-normalized via the FCFS baseline) and
sub-linear in W.

``run_million_jobs`` is the campaign-scale throughput suite (ISSUE 10):
a J=10^6 synthetic-SWF stream through the chunked ``totals_only``
campaign path, recorded as a jobs/sec RATE so the CI smoke re-run at
reduced J (``SCHED_BENCH_MILLION_J``) gates against the committed
million-job number, plus an 8-virtual-device shard_map-vs-vmap ratio.

Run as a module (``python benchmarks/scheduler_ablation.py``) to also
write ``BENCH_scheduler.json`` (every row + per-point wall-clock; rows
that only carry derived metrics are marked ``"timed": false``) at the
repo root, so the scheduler perf trajectory is tracked across commits —
``tests/test_bench_guard.py`` gates regressions against the committed
rows in CI.
"""

from __future__ import annotations

import json
import os
import pathlib
import statistics
import subprocess
import sys
import time

import jax
import numpy as np

from repro.core import (JSCC_SYSTEMS, FaultConfig, Scheduler, make_policy,
                        policy_names)
from repro.core.engine import _batched_run
from repro.core.systems import ComputeSystem
from repro.launch.compile_cache import enable_compile_cache
from repro.data.scenarios import (load_swf, make_stream_workload,
                                  swf_lines, synthetic_swf_arrays,
                                  workload_from_arrays, workload_from_trace)

KS = (0.05, 0.10, 0.20)
SEEDS = (0, 1)

#: PR 3's committed warm wall-clock (BENCH_scheduler.json @ 9d6f3dd) for
#: the python-unrolled EASY scan at W=16 on the SWF stream, with its FCFS
#: row as the machine-speed anchor.  The batched candidate evaluation
#: (ISSUE 4) must beat the unrolled number by >= 5x; the anchor converts
#: that bar to the machine actually running the benchmark.
PR3_EASY_W16_US = 1_357_624.3
PR3_FCFS_US = 31_567.4


def _warm_us(sched, w, repeats: int = 3):
    """Warm wall-clock of one ``Scheduler.run``: first call compiles, then
    best-of-``repeats`` timed calls (device-synced) — the scan, not XLA
    compilation or scheduler noise.  Returns ``(microseconds, result)``
    with the last run's result, so callers read metrics without paying
    for yet another simulation."""
    jax.block_until_ready(sched.run(w).total_energy)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = sched.run(w)
        jax.block_until_ready(res.total_energy)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6, res


def machine_speed_factor(fresh_fcfs_us: float, anchor_us: float) -> float:
    """How much slower this machine is than the one that produced the
    anchor FCFS measurement.  Unclamped on purpose: scaling a bound by
    this ratio makes it machine-invariant in both directions (a faster
    machine shrinks the absolute bound proportionally), and best-of-N
    warm timing cannot fluke *below* the hardware's real speed, so a
    ratio < 1 always means genuinely faster hardware."""
    return fresh_fcfs_us / anchor_us


def _stream(n_jobs=200, seed=0):
    return make_stream_workload(JSCC_SYSTEMS, n_jobs, arrival="bursty",
                                rate=0.125, seed=seed, pred_noise=0.10)


def run():
    w = _stream()
    rows = []
    for name in policy_names():
        if name == "oracle":
            continue                   # identical to paper on clean tables
        pol = make_policy(name, k=np.asarray(KS, np.float32))
        t0 = time.perf_counter()
        res = Scheduler(pol, seeds=SEEDS).run(w)   # cold start: tables empty
        e = float(np.asarray(res.total_energy).mean())
        m = float(np.asarray(res.makespan).mean())
        wsum = float(np.asarray(res.total_wait).mean())
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"ablate_{name}", us,
                     f"E={e/1e3:.0f}kJ;makespan={m:.0f}s;wait={wsum:.0f}s"
                     f";grid={len(KS)}Kx{len(SEEDS)}seed"))
    return rows


def run_policy_grid():
    """One jitted ``Scheduler.run`` over a 32-point K x ucb-scale
    hyperparameter mesh (leaf-batched Policy): no re-trace per point."""
    w = _stream(n_jobs=150, seed=2)
    kk, uu = np.meshgrid(np.linspace(0.0, 0.35, 8).astype(np.float32),
                         np.asarray([0.25, 0.5, 0.75, 1.0], np.float32))
    pol = make_policy("ucb", k=kk.ravel(), ucb_scale=uu.ravel())
    cache0 = _batched_run._cache_size()
    t0 = time.perf_counter()
    res = Scheduler(pol, seeds=0).run(w, totals_only=True)
    E = np.asarray(res.total_energy)                        # [32]
    us = (time.perf_counter() - t0) * 1e6
    traced = _batched_run._cache_size() - cache0
    assert traced <= 1, f"grid re-traced: {traced} compilations"
    best = int(E.argmin())
    return [("policy_grid_32pt", us,
             f"points={E.size};compiles={traced};best_E={E[best]/1e3:.0f}kJ"
             f"@K={kk.ravel()[best]:.2f},ucb={uu.ravel()[best]:.2f}")]


def _synthetic_swf(n=250, seed=11):
    """A contended SWF-style trace: heavy-tailed runtimes and node counts
    with clustered submits — the workload shape EASY backfilling was made
    for (long wide head jobs blocking short narrow ones).  Round-trips
    the scenario library's column generator through the SWF text format
    (the loader is part of what the queue bench exercises)."""
    return load_swf(swf_lines(*synthetic_swf_arrays(n, seed)))


def queue_streams():
    """The two contended scenario streams of the queue ablation."""
    return {
        "swf": workload_from_trace(_synthetic_swf(), JSCC_SYSTEMS),
        "diurnal": make_stream_workload(JSCC_SYSTEMS, 300, arrival="diurnal",
                                        rate=0.8, seed=3, pred_noise=0.05),
    }


def run_queue_disciplines():
    """FCFS vs EASY vs conservative backfilling (paper selection rule,
    warm tables) on SWF-replay and diurnal streams; every (stream,
    discipline) point is timed individually.  Asserted acceptance
    criteria: EASY strictly improves mean wait over FCFS on at least one
    stream (ISSUE 3), and conservative — hole-aware reservations on the
    event-granular core — strictly improves mean wait over EASY on BOTH
    streams (ISSUE 5: the interval reservation table exposes the idle
    gaps under every pending job, where EASY only sees the head's)."""
    rows = []
    improved = []
    cons_beats_easy = []
    for tag, w in queue_streams().items():
        waits = {}
        for queue in ("fcfs", "easy_backfill:window=16",
                      "conservative:window=16"):
            qname = queue.split(":")[0]
            sched = Scheduler(make_policy("paper", k=0.10), warm_start=True,
                              queue=queue)
            us, res = _warm_us(sched, w)
            mw = float(np.asarray(res.mean_wait))
            waits[qname] = mw
            rows.append((
                f"queue_{tag}_{qname}", us,
                f"mean_wait={mw:.1f}s;max_wait={float(res.max_wait):.0f}s"
                f";makespan={float(res.makespan):.0f}s"
                f";backfill_rate={float(res.backfill_rate):.2f}"
                f";util={float(np.asarray(res.utilization).mean()):.2f}"))
        improved.append(waits["easy_backfill"] < waits["fcfs"])
        cons_beats_easy.append(waits["conservative"] < waits["easy_backfill"])
        rows.append((f"queue_{tag}_delta", 0.0,
                     f"dwait={100 * (waits['easy_backfill'] / waits['fcfs'] - 1):+.1f}%"))
        rows.append((
            f"queue_{tag}_cons_delta", 0.0,
            f"dwait_vs_easy="
            f"{100 * (waits['conservative'] / waits['easy_backfill'] - 1):+.1f}%"))
    assert any(improved), \
        "EASY backfilling improved mean wait on no stream (acceptance)"
    assert all(cons_beats_easy), \
        "conservative backfilling must strictly improve mean wait over " \
        "EASY on every ablation stream (ISSUE 5 acceptance)"
    return rows


#: Power-cap sweep grid (Watts).  The JSCC model's all-idle floor is
#: ~32.8 kW and the uncapped peak on the diurnal stream is ~66 kW, so the
#: grid spans comfortably-binding to effectively-uncapped.
POWER_CAPS = (45_000.0, 52_000.0, 60_000.0, 1e30)


def run_power_caps():
    """SCC power-cap sweep (ISSUE 5): the whole cap grid is ONE
    leaf-batched policy simulated in a single jitted call (power_cap is a
    Policy leaf, like k/ucb_scale).  Asserted: every binding cap yields
    peak_power <= cap, and tightening the cap never reduces makespan
    (the runtime side of the paper's power/performance trade-off)."""
    w = queue_streams()["diurnal"]
    caps = np.asarray(POWER_CAPS, np.float32)
    pol = make_policy("paper", k=0.10, power_cap=caps)
    sched = Scheduler(pol, warm_start=True)
    us, res = _warm_us(sched, w)
    peak = np.asarray(res.peak_power)
    mk = np.asarray(res.makespan)
    cdel = np.asarray(res.capped_delay)
    idle = np.asarray(res.idle_energy)
    energy = np.asarray(res.total_energy)
    rows = [("power_cap_sweep", us,
             f"grid={len(caps)}caps;one_jit_call;uncapped_peak="
             f"{peak[-1] / 1e3:.1f}kW")]
    for i, cap in enumerate(caps):
        tag = "uncapped" if cap >= 1e29 else f"{int(cap / 1000)}kW"
        rows.append((
            f"power_cap_{tag}", 0.0,
            f"peak={peak[i] / 1e3:.1f}kW;makespan={mk[i]:.0f}s"
            f";capped_delay={cdel[i]:.0f}s;energy={energy[i] / 1e6:.2f}MJ"
            f";idle_energy={idle[i] / 1e6:.2f}MJ"))
        if cap < 1e29:
            assert peak[i] <= cap * (1 + 1e-5), \
                f"peak_power {peak[i]:.0f} exceeds cap {cap:.0f} (acceptance)"
    # tightening the cap never reduces makespan (monotone trade-off,
    # small tolerance for f32 scheduling ties)
    assert (mk[:-1] >= mk[1:] * (1 - 1e-4)).all(), \
        f"makespan not monotone under tightening caps: {mk}"
    return rows


def run_window_scaling():
    """EASY window-scaling sweep on the contended SWF stream: warm
    wall-clock for W in {4, 8, 16, 32} with the (W-independent) FCFS
    baseline.  Two asserted properties of the batched candidate
    evaluation (ISSUE 4):

    - >= 5x faster at W=16 than the PR 3 unrolled loop's committed row
      (the hard-coded ``PR3_*`` anchors, normalized to this machine's
      speed through the FCFS baseline);
    - sub-linear cost growth in W: the 8x window increase 4 -> 32 must
      cost well under 8x (one shared sort + one [W, maxN] row query per
      step, so the per-step kernel work barely scales with W).
    """
    w = queue_streams()["swf"]
    pol = make_policy("paper", k=0.10)
    fcfs_us, _ = _warm_us(Scheduler(pol, warm_start=True), w)
    rows = [("queue_window_fcfs", fcfs_us, "baseline;window-independent")]
    by_w = {}
    for window in (4, 8, 16, 32):
        sched = Scheduler(pol, warm_start=True,
                          queue=f"easy_backfill:window={window}")
        us, res = _warm_us(sched, w)
        by_w[window] = us
        rows.append((
            f"queue_window_w{window}", us,
            f"mean_wait={float(res.mean_wait):.1f}s"
            f";backfill_rate={float(res.backfill_rate):.2f}"
            f";x_fcfs={us / fcfs_us:.1f}"))
    speed = machine_speed_factor(fcfs_us, PR3_FCFS_US)
    gain = PR3_EASY_W16_US * speed / by_w[16]
    rows.append(("queue_window_gain_vs_pr3", 0.0,
                 f"gain={gain:.1f}x;speed_factor={speed:.2f}"
                 f";w32_over_w4={by_w[32] / by_w[4]:.2f}"))
    assert gain >= 5.0, (
        f"batched EASY at W=16 is only {gain:.1f}x faster than the PR 3 "
        f"committed row (>= 5x required): {by_w[16]:.0f}us vs "
        f"{PR3_EASY_W16_US:.0f}us @ speed factor {speed:.2f}")
    assert by_w[32] < 8.0 * by_w[4], (
        f"window cost not sub-linear: W=32 {by_w[32]:.0f}us vs "
        f"W=4 {by_w[4]:.0f}us (8x window must cost < 8x)")
    return rows


def run_fault_tolerance():
    """Same stream under a straggler/failure grid: the history mechanism
    routes around degraded systems (fault tolerance, DESIGN.md §7).  The
    whole fault grid is one ``Scheduler.run``."""
    w = _stream(seed=1)
    grid = [
        ("clean", FaultConfig()),
        ("stragglers", FaultConfig(straggler_prob=0.15, straggler_factor=2.5)),
        ("failures", FaultConfig(failure_prob=0.10, restart_overhead=0.5)),
    ]
    pol = make_policy("paper", k=np.asarray([0.10], np.float32))
    t0 = time.perf_counter()
    res = Scheduler(pol, seeds=SEEDS, faults=[f for _, f in grid]).run(w)
    us = (time.perf_counter() - t0) * 1e6
    E = np.asarray(res.total_energy)          # [F, K, R]
    M = np.asarray(res.makespan)
    # the grid is ONE jitted call — time it once; per-config rows carry
    # metrics only (a per-config split of the shared call would be fiction)
    rows = [("fault_grid", us,
             f"configs={len(grid)};seeds={len(SEEDS)};one_jit_call")]
    for i, (tag, _) in enumerate(grid):
        rows.append((f"fault_{tag}", 0.0,
                     f"E={E[i].mean()/1e3:.0f}kJ;makespan={M[i].mean():.0f}s"))
    return rows


def run_service():
    """Online service decision latency (ISSUE 7): a live ``Dispatcher``
    replays the contended SWF stream event-by-event — each job submitted
    before the clock is driven past its arrival — through the SAME jitted
    step the batch scan folds.  Bit-identity of the realized totals
    against the batch ``Scheduler.run`` is asserted (the service
    acceptance criterion); the row records the warm per-decision latency
    (the one compile-paying step is excluded as the latency maximum)."""
    from repro.service import Dispatcher, own_columns

    w = queue_streams()["swf"]
    pol = make_policy("paper", k=0.10)
    qs = "easy_backfill:window=16"
    batch = Scheduler(pol, warm_start=True, queue=qs, engine="events").run(w)
    disp = Dispatcher(w, pol, warm_start=True, queue=qs)
    for j in range(len(w.prog)):
        disp.drive(until=float(w.arrival[j]))
        disp.submit(int(w.prog[j]), float(w.arrival[j]),
                    **own_columns(w, j))
    disp.drain()
    res = disp.result()
    for f in ("total_energy", "makespan", "total_wait", "max_wait",
              "peak_power", "idle_energy", "n_backfilled"):
        a, b = np.asarray(getattr(batch, f)), np.asarray(getattr(res, f))
        assert a.tobytes() == b.tobytes(), \
            f"live session diverged from batch on {f}: {b} != {a}"
    m = disp.metrics
    warm_us = (m.latency_us_total - m.latency_us_max) / max(m.n_steps - 1, 1)
    return [("service_decision_latency", warm_us,
             f"steps={m.n_steps};jobs={m.n_finished}"
             f";compile_us={m.latency_us_max:.0f}"
             f";peak={m.peak_power / 1e3:.1f}kW;bit_identical=True")]


def run_pool():
    """Pooled decision latency (ISSUE 9): N sessions replay the
    contended SWF stream concurrently through ONE jitted vmapped step
    (repro.service.SessionPool).  Every lane's totals are asserted
    bit-identical to the batch run, and the per-decision cost (warm
    pool-step wall / N) must scale SUB-linearly in N — the vmapped step
    amortizes dispatch and device traffic across the whole pool."""
    from repro.service import SessionPool, own_columns

    w = queue_streams()["swf"]
    pol = make_policy("paper", k=0.10)
    qs = "easy_backfill:window=16"
    batch = Scheduler(pol, warm_start=True, queue=qs, engine="events").run(w)
    per_dec = {}
    for n in (1, 4, 8):
        pool = SessionPool.replicate(
            Scheduler(pol, warm_start=True, queue=qs), n, w)
        for j in range(len(w.prog)):
            t = float(w.arrival[j])
            pool.drive(t)
            for i in range(n):
                pool.submit(i, int(w.prog[j]), t, **own_columns(w, j))
        pool.drain()
        for i in range(n):
            res = pool.result(i)
            for f in ("total_energy", "makespan", "total_wait"):
                a = np.asarray(getattr(batch, f))
                b = np.asarray(getattr(res, f))
                assert a.tobytes() == b.tobytes(), \
                    f"pool lane {i}/{n} diverged from batch on {f}: {b} != {a}"
        warm = ((pool.wall_us_total - pool.wall_us_max)
                / max(pool.n_pool_steps - 1, 1))
        per_dec[n] = warm / n
        pool.close()
    assert per_dec[8] < per_dec[1], \
        f"pool per-decision cost scaled super-linearly: {per_dec}"
    return [("pool_decision_latency", per_dec[8],
             f"n1={per_dec[1]:.0f}us;n4={per_dec[4]:.0f}us"
             f";n8={per_dec[8]:.0f}us"
             f";scaling_x8={per_dec[8] / per_dec[1]:.2f}"
             f";bit_identical=True")]


def run_dvfs_pareto():
    """DVFS x selection Pareto lattice (ISSUE 8): one leaf-batched
    ``Scheduler.run`` over a (power_cap x freq_weight x K) grid of the
    ``dvfs_paper`` policy; frontier extraction, single-compilation and
    baseline-domination assertions live in benchmarks/dvfs_pareto.py."""
    import dvfs_pareto
    return dvfs_pareto.run()


#: Million-job campaign suite (ISSUE 10).  ``SCHED_BENCH_MILLION_J``
#: shrinks the trace for CI smoke runs; the committed row is the full
#: J=10^6.  The throughput row records a RATE (simulated job-decisions
#: per second across the whole grid), so reduced-J re-measurements stay
#: comparable to the committed million-job number.
MILLION_J = int(os.environ.get("SCHED_BENCH_MILLION_J", "1000000"))
MILLION_CHUNK = 65_536

#: A deliberately small two-system cluster for the million-job rows: the
#: per-step cost scales with max nodes/system, and the point of the suite
#: is job-stream THROUGHPUT, not cluster size.
SMALL_CAMPAIGN = (
    ComputeSystem(name="alpha", n_nodes=8, cores_per_node=64,
                  peak_flops_node=2e12, mem_bw_node=200e9, net_bw_node=10e9,
                  disk_bw_node=2e9, idle_w=100.0, cpu_w=200.0, net_w=20.0,
                  disk_w=10.0, efficiency=0.5),
    ComputeSystem(name="beta", n_nodes=12, cores_per_node=48,
                  peak_flops_node=1.2e12, mem_bw_node=150e9, net_bw_node=8e9,
                  disk_bw_node=1.5e9, idle_w=80.0, cpu_w=160.0, net_w=15.0,
                  disk_w=8.0, efficiency=0.55),
)


def million_workload(J):
    """Synthetic-SWF million-job stream on the small campaign cluster."""
    return workload_from_arrays(*synthetic_swf_arrays(int(J), seed=11),
                                SMALL_CAMPAIGN)


def _median_campaign_sec(sched, w, repeats: int = 3) -> float:
    """Warm median-of-``repeats`` wall-clock of one totals_only campaign
    call (first call pays compilation and is discarded)."""
    jax.block_until_ready(sched.run(w, totals_only=True).total_energy)
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = sched.run(w, totals_only=True)
        jax.block_until_ready(res.total_energy)
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples)


def _shard_scaling_row(J):
    """Sharded-vs-single-device wall-clock ratio on an 8-virtual-device
    CPU mesh (subprocess: the XLA device-count flag must be set before
    jax initializes).  The child is pinned to the CPU
    (``JAX_PLATFORMS=cpu``) so it never contends for a chip its parent
    holds, and its row says ``platform=cpu``.  The ratio is
    machine-invariant — both sides run on the same box in the same
    process — so it is gated directly: sharding
    the grid must never cost more than GATE x the single-device vmap
    (on a multi-core runner it should win; 8 virtual devices on one
    physical core merely round-trip through shard_map)."""
    Js = min(int(J), 200_000)
    script = f"""
import json, statistics, time
import jax
import numpy as np
from scheduler_ablation import (MILLION_CHUNK, SEEDS, _median_campaign_sec,
                                million_workload)
from repro.core import Scheduler, make_policy

w = million_workload({Js})
ks = np.linspace(0.0, 0.3, 4).astype(np.float32)
def med(**kw):
    s = Scheduler(make_policy("paper", k=ks), warm_start=True, seeds=SEEDS,
                  chunk=MILLION_CHUNK, **kw)
    return _median_campaign_sec(s, w)
single = med()
sharded = med(shards="auto")
print(json.dumps({{"platform": jax.devices()[0].platform,
                   "devices": len(jax.devices()),
                   "single_us": single * 1e6,
                   "sharded_us": sharded * 1e6}}))
"""
    here = pathlib.Path(__file__).resolve().parent
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"    # never contend for the parent's chip
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = f"{here.parent / 'src'}:{here}"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.splitlines()[-1])
    ratio = rep["sharded_us"] / rep["single_us"]
    return [("campaign_shard_scaling", rep["sharded_us"],
             f"platform={rep['platform']};devices={rep['devices']}"
             f";jobs={Js};lanes=8"
             f";single_us={rep['single_us']:.0f}"
             f";ratio_vs_single={ratio:.2f}")]


def run_million_jobs(J=None):
    """Million-job campaign throughput (ISSUE 10): an 8-lane (K x seed)
    grid over a J=10^6 synthetic-SWF stream, chunked (``chunk=65536``) and
    ``totals_only`` so no [grid, J] array is ever materialized.  The
    timed row is the warm median-of-3 campaign call; its derived
    ``jobs_per_sec`` rate (grid lanes x J / seconds) is what the CI gate
    compares, so reduced-J smoke runs measure the same quantity as the
    committed million-job row.  The companion ``campaign_shard_scaling``
    row measures the 8-virtual-device shard_map against the single-device
    vmap in a subprocess."""
    J = int(J or MILLION_J)
    w = million_workload(J)
    ks = np.linspace(0.0, 0.3, 4).astype(np.float32)
    lanes = len(ks) * len(SEEDS)
    sched = Scheduler(make_policy("paper", k=ks), warm_start=True,
                      seeds=SEEDS, chunk=MILLION_CHUNK)
    sec = _median_campaign_sec(sched, w)
    rate = lanes * J / sec
    rows = [("campaign_jobs_per_sec", sec * 1e6,
             f"jobs={J};lanes={lanes};chunk={MILLION_CHUNK}"
             f";jobs_per_sec={rate:.0f};totals_only=True")]
    rows += _shard_scaling_row(J)
    return rows


#: The module's suite registry — the single source for both harnesses
#: (benchmarks/run.py spreads it into its suite list; main() below writes
#: the same rows to BENCH_scheduler.json).
SUITES = (("ablation", run),
          ("policy_grid", run_policy_grid),
          ("fault_tolerance", run_fault_tolerance),
          ("queue_disciplines", run_queue_disciplines),
          ("window_scaling", run_window_scaling),
          ("power_caps", run_power_caps),
          ("service", run_service),
          ("pool", run_pool),
          ("dvfs_pareto", run_dvfs_pareto),
          ("million_jobs", run_million_jobs))


def main(argv=None):
    """Run the ablation suites (all by default; ``--suites a,b`` for a
    subset — the bench-smoke PR job runs only the queue suites), print
    the CSV, and persist the rows (with per-point wall-clock) to
    BENCH_scheduler.json at the repo root.  Rows that only carry derived
    metrics (no wall-clock of their own) are marked ``"timed": false``
    so the regression gate and averaging tools never mistake their 0.0
    for a measurement."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--suites", default="",
                    help="comma-separated subset of: "
                         + ",".join(n for n, _ in SUITES))
    args = ap.parse_args(argv)
    enable_compile_cache()
    wanted = set(args.suites.split(",")) if args.suites else None
    if wanted is not None:
        unknown = wanted - {n for n, _ in SUITES}
        if unknown:
            ap.error(f"unknown suites {sorted(unknown)}")
    rows = []
    print("name,us_per_call,derived")
    for name, fn in SUITES:
        if wanted is not None and name not in wanted:
            continue
        for row in fn():
            rows.append(row)
            print(f"{row[0]},{row[1]:.1f},{row[2]}")
    fresh = []
    for n, us, d in rows:
        row = {"name": n, "timed": us > 0, "derived": d}
        if us > 0:
            # derived-only rows OMIT us_per_call entirely — a phantom 0.0
            # reads like "this took no time" to averaging tools
            row = {"name": n, "us_per_call": round(us, 1), "timed": True,
                   "derived": d}
        fresh.append(row)
    out = pathlib.Path(__file__).resolve().parent.parent / "BENCH_scheduler.json"
    if wanted is not None and out.exists():
        # subset runs refresh their own rows IN the existing file — never
        # drop the other suites' committed rows from the artifact
        by_name = {r["name"]: r for r in fresh}
        old = json.loads(out.read_text())["rows"]
        fresh = [by_name.pop(r["name"], r) for r in old] + list(by_name.values())
    payload = {
        "bench": "scheduler",
        "generated_unix": time.time(),
        "rows": fresh,
    }
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
