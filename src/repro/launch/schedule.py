"""Scheduler CLI: run the EcoSched simulator on a job stream or campaign.

Policies come from the registry (``repro.core.policy``); pick one with
``--policy name`` or ``--policy name:key=val,...`` (hyperparameters parse
as floats), e.g.:

    PYTHONPATH=src python -m repro.launch.schedule --policy paper:k=0.1
    PYTHONPATH=src python -m repro.launch.schedule \
        --policy ucb:k=0.1,ucb_scale=0.25

(the legacy ``--mode NAME --k F`` spelling still works).

Queue discipline (``--queue``): placement order over the pending queue —
``fcfs`` (strict arrival order, the paper), EASY backfilling with a
bounded pending window, or conservative backfilling (every pending job's
reservation guarded, on the event-granular core)::

    PYTHONPATH=src python -m repro.launch.schedule --jobs 200 \
        --scenario diurnal --queue easy_backfill:window=16
    PYTHONPATH=src python -m repro.launch.schedule --jobs 200 \
        --scenario diurnal --queue conservative:window=16

SCC power cap (``--power-cap``, Watts): the paper's motivating grid
limit.  Placements are deferred while the cluster's instantaneous draw
(busy-job power + idle watts of unallocated nodes) would exceed the cap;
runs on the event-granular core and reports peak_power / capped_delay /
idle_energy::

    PYTHONPATH=src python -m repro.launch.schedule --jobs 200 \
        --scenario bursty --queue conservative --power-cap 60000

Single run / K sweep (the paper's Figs 1-4 regime):

    PYTHONPATH=src python -m repro.launch.schedule --policy paper:k=0.1
    PYTHONPATH=src python -m repro.launch.schedule --sweep-k 0,0.05,0.1,0.2

Campaign grid — ONE jitted ``Scheduler.run`` simulates the whole
(K grid x seed grid) over a scenario-generated job stream:

    PYTHONPATH=src python -m repro.launch.schedule \
        --jobs 10000 --scenario poisson --arrival-rate 0.5 \
        --campaign-k 0,0.05,0.1,0.2,0.3 --campaign-seeds 4 --totals-only

Trace replay (SWF; ``.gz`` ok, ``--calibrate-trace`` maps classes through
the phase model instead of raw node throughput):

    PYTHONPATH=src python -m repro.launch.schedule --trace my_log.swf.gz \
        --campaign-k 0,0.1,0.3 --campaign-seeds 2

Million-job scale-out: ``--shards auto|N`` spreads the campaign grid over
the local devices (shard_map on the ("grid",) mesh) and ``--chunk SIZE``
streams the event scan in fixed windows so a J=10^6 trace never
materializes a [grid, J] intermediate (pair with ``--totals-only`` for
O(1) per-job memory):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.schedule \
        --jobs 1000000 --scenario poisson --arrival-rate 0.5 \
        --campaign-k 0,0.1 --campaign-seeds 4 --totals-only \
        --shards auto --chunk 65536

Facade (repro.core.Scheduler):
    Scheduler(policy, placer=..., faults=..., seeds=...).run(w,
    totals_only=...) -> SimResult / CampaignResult with named leading axes
    (fault, policy, seed), derived metrics (mean slowdown, per-system
    utilization), and ``.to_dict()``.  Everything runs in a single jit; the
    placement inner loop is the kth-free-time radix-select kernel
    (repro.kernels.kth_free), not a per-step sort.  ``--totals-only`` keeps
    per-job arrays out of memory on big grids (campaign memory).

Scenario formats (repro.data.scenarios):
    --scenario {simultaneous, poisson, diurnal, bursty}  — arrival process
      (diurnal: sinusoidal day/night rate; bursty: Poisson bursts of
      correlated array-job submissions), mixed NPB job-size classes drawn
      per --mix-small weight.
    --trace FILE — Standard Workload Format replay: 18 whitespace-separated
      fields per line, ';' comments; submit/runtime/procs are consumed and
      jobs are binned into learned program classes
      (repro.data.scenarios.workload_from_trace).
    --outage S:START:END (repeatable) — maintenance window on system index
      S; no new placements start inside [START, END).
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core import (JSCC_SYSTEMS, FaultConfig, Scheduler,
                        make_npb_workload)
from repro.core.cliargs import (add_policy_options, add_scale_options,
                                build_engine, build_policy, build_scale)
from repro.data.scenarios import (make_stream_workload, maintenance_windows,
                                  load_swf, workload_from_trace,
                                  NPB_SMALL, NPB_LARGE, ARRIVAL_KINDS)
from repro.launch.compile_cache import enable_compile_cache


def _parse_outages(specs, n_systems):
    if not specs:
        return None
    spans = {}
    for spec in specs:
        s, a, b = spec.split(":")
        spans.setdefault(int(s), []).append((float(a), float(b)))
    return maintenance_windows(n_systems, spans)


def build_workload(args):
    outage = _parse_outages(args.outage, len(JSCC_SYSTEMS))
    if args.trace:
        w = workload_from_trace(load_swf(args.trace), JSCC_SYSTEMS,
                                calibrate=args.calibrate_trace)
        if outage is not None:
            from dataclasses import replace
            w = replace(w, outage=outage)
        return w
    if args.jobs:
        mix = {NPB_SMALL: args.mix_small, NPB_LARGE: 1.0 - args.mix_small}
        return make_stream_workload(
            JSCC_SYSTEMS, args.jobs, arrival=args.scenario,
            rate=args.arrival_rate, mix=mix, seed=args.seed, outage=outage)
    return make_npb_workload(JSCC_SYSTEMS, outage=outage)


def main():
    ap = argparse.ArgumentParser()
    add_policy_options(ap, engine=True)     # the shared grammar (cliargs)
    add_scale_options(ap)                   # --shards / --chunk
    ap.add_argument("--sweep-k", default="",
                    help="comma-separated K values (fractions)")
    ap.add_argument("--jobs", type=int, default=0,
                    help="stream length (default: the paper's 5-job suite)")
    ap.add_argument("--scenario", default="poisson", choices=ARRIVAL_KINDS,
                    help="arrival process for --jobs streams")
    ap.add_argument("--arrival-rate", type=float, default=0.125,
                    help="mean arrivals per second (0 = simultaneous)")
    ap.add_argument("--mix-small", type=float, default=0.5,
                    help="weight of the small NPB job-size class")
    ap.add_argument("--trace", default="",
                    help="SWF trace file to replay instead of synthetic "
                         "jobs (.gz transparently gunzipped)")
    ap.add_argument("--calibrate-trace", action="store_true",
                    help="calibrate replayed job classes against the "
                         "phase model (workload_model.predict_phases) "
                         "instead of raw node throughput")
    ap.add_argument("--outage", action="append", default=[],
                    metavar="S:T0:T1",
                    help="maintenance window on system S (repeatable)")
    ap.add_argument("--campaign-k", default="",
                    help="comma-separated K grid -> one-jit campaign")
    ap.add_argument("--campaign-seeds", type=int, default=0,
                    help="number of seeds in the campaign grid")
    ap.add_argument("--totals-only", action="store_true",
                    help="campaign memory: aggregate metrics only, no "
                         "per-job arrays (for huge job x grid products)")
    ap.add_argument("--cold", action="store_true",
                    help="empty profile tables (exploration phase)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    w = build_workload(args)
    pol = build_policy(args)
    engine = build_engine(args)
    scale = build_scale(args)
    faults = FaultConfig(straggler_prob=args.stragglers,
                         failure_prob=args.failures)

    if args.campaign_k:
        ks = np.array([float(x) for x in args.campaign_k.split(",")],
                      np.float32)
        seeds = [args.seed + i for i in range(max(args.campaign_seeds, 1))]
        res = Scheduler(pol.with_params(k=ks), faults=faults, seeds=seeds,
                        warm_start=not args.cold, engine=engine,
                        **scale).run(w, totals_only=args.totals_only)
        E = np.asarray(res.total_energy)            # [K, R]
        M = np.asarray(res.makespan)
        W = np.asarray(res.total_wait)
        print(f"campaign: jobs={res.n_jobs} grid={len(ks)}Kx{len(seeds)}seed "
              f"policy={pol.name} axes={res.axes}")
        print("K,energy_J(mean),energy_J(std),makespan_s(mean),wait_s(mean),dE%")
        for i, k in enumerate(ks):
            print(f"{k:.2f},{E[i].mean():.0f},{E[i].std():.0f},"
                  f"{M[i].mean():.1f},{W[i].mean():.1f},"
                  f"{100*(E[i].mean()-E[0].mean())/E[0].mean():+.1f}")
        return

    if args.sweep_k:
        ks = np.array([float(x) for x in args.sweep_k.split(",")], np.float32)
        res = Scheduler(pol.with_params(k=ks), faults=faults,
                        seeds=args.seed, warm_start=not args.cold,
                        engine=engine, **scale).run(w)
        E = np.asarray(res.total_energy)
        M = np.asarray(res.makespan)
        print("K,energy_J,makespan_s,dE%,dT%")
        for i, k in enumerate(ks):
            print(f"{k:.2f},{E[i]:.0f},{M[i]:.1f},"
                  f"{100*(E[i]-E[0])/E[0]:+.1f},{100*(M[i]-M[0])/M[0]:+.1f}")
        return

    r = Scheduler(pol, faults=faults, seeds=args.seed,
                  warm_start=not args.cold, engine=engine, **scale).run(w)
    sel = np.asarray(r.system)
    k_str = np.format_float_positional(float(np.asarray(pol.k)), trim="-")
    q_str = pol.queue if pol.queue == "fcfs" else \
        f"{pol.queue}(window={pol.window})"
    print(f"policy={pol.name} K={k_str} queue={q_str} jobs={r.n_jobs} "
          f"warm={not args.cold}")
    print(f"energy={float(r.total_energy)/1e3:.1f} kJ  "
          f"makespan={float(r.makespan):.1f} s  "
          f"total_wait={float(r.total_wait):.1f} s  "
          f"mean_slowdown={float(r.mean_slowdown):.2f}  "
          f"backfill_rate={float(r.backfill_rate):.1%}")
    peak = float(r.peak_power)
    if not np.isnan(peak):                 # event-granular core: SCC power
        cap_str = f"{args.power_cap:.0f} W" if args.power_cap else "none"
        print(f"peak_power={peak/1e3:.1f} kW (cap {cap_str})  "
              f"capped_delay={float(r.capped_delay):.1f} s  "
              f"idle_energy={float(r.idle_energy)/1e3:.1f} kJ")
    counts = np.bincount(sel, minlength=len(w.systems))
    print("placements:", {w.systems[i]: int(c) for i, c in enumerate(counts)})
    util = np.asarray(r.utilization)
    print("utilization:", {w.systems[i]: f"{u:.1%}" for i, u in enumerate(util)})


if __name__ == "__main__":
    main()
