#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one result line.

    python3 bench/run.py --workload jscc4.campaign-fcfs --seed 7 \
        --seconds 30 --trace 0

Loads the cell's configuration and traffic from the files that
``BENCHMARK.json`` names, builds the inputs from ``--seed``, warms up
every shape the window uses (all of that is ``setup_s``), measures for
``--seconds``, checks what the window produced against the plain
reference in ``bench/reference/``, and prints the result as the last
line of standard output.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` takes a profiler trace of a stretch of the window
and reports its per-layer metrics.  Without a TPU, or with fewer chips
than the cell needs, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    device = harness.require_devices(cell["chips"])
    return run_cell(cell, device, args.seed, args.seconds, bool(args.trace))


def run_cell(cell: dict, device: dict, seed: int, seconds: float,
             trace: bool) -> int:
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program the window uses is cached, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = harness.SetupClock()
    kind = harness.kind_module(cell)
    run = kind.Cell(cell, seed)
    run.setup()
    setup_s = time.perf_counter() - T_START
    compiles0 = clock.compiles

    tracer = harness.Tracer(trace, ROOT / ".bench_trace" / cell["name"])
    window = run.measure(seconds, tracer)
    tracer.stop()
    in_window = clock.compiles - compiles0
    note = lambda msg: print(msg, file=sys.stderr, flush=True)
    note(f"set-up {setup_s:.3f} s: {compiles0} compiles, "
         f"{clock.cache_hits} from the persistent cache, "
         f"{clock.compile_s:.3f} s lowering and compiling")
    note(f"compiles inside the window: {in_window}")
    for line in window.get("notes", ()):
        note(line)

    devices = jax.devices()[:cell["chips"]]
    device = {**device,
              "memory_peak_bytes": harness.memory_peak_bytes(devices)}
    run.free()
    checks = run.check()
    correct = all(c["value"] <= c["limit"] for c in checks) \
        and window["failed"] == 0

    if trace:
        from bench import trace_reduce
        path = tracer.xplane()
        reduced = (trace_reduce.reduce(path, tracer.WINDOW_SPAN)
                   if path is not None else None)
        ctx = {"trace": reduced, "counters": window["counters"],
               "peaks": harness.peaks(device["kind"]),
               "compile_s": clock.compile_s}
        metrics = {}
        for m in cell["per_layer"]:
            v = harness.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
    else:
        values = {**window["metrics"], "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}

    for c in checks:
        note(f"check {c['name']} = {c['value']!r} (limit {c['limit']!r})")
    line = {"correct": bool(correct), "attempted": window["attempted"],
            "failed": window["failed"], "metrics": metrics,
            "device": device}
    if trace and reduced is not None:
        line["breakdown"] = reduced.breakdown()
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
