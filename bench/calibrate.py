#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload jscc4.campaign-fcfs \
        --seeds 12 --controls 3 --seconds 1

In one process (the programs compile once): for each of ``--seeds``
seeds, set the cell up, run a short window at the cell's own size and
load, and print the numbers ``correct`` compares; for the first
``--controls`` of them, also the numbers when the reference computed in
bfloat16 stands in the program's place.  The lower reading of a number
is the largest of the program's, the upper the smallest of the
control's.  Prints one JSON line per seed and a summary line.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402


def readings(cell: dict, seeds, controls: int, seconds: float):
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    kind = harness.kind_module(cell)
    prog, ctl = {}, {}
    for n, seed in enumerate(seeds):
        run = kind.Cell(cell, seed)
        run.setup()
        run.measure(seconds, harness.Tracer(False, ROOT))
        run.free()
        got = {c["name"]: c["value"] for c in run.check()}
        row = {"seed": seed, "program": got,
               "per_field": getattr(run, "per_field", None)}
        for k, v in got.items():
            prog[k] = max(prog.get(k, 0.0), v)
        if n < controls:
            c = run.control()
            row["control"] = c
            row["control_per_field"] = run.per_field
            for k, v in c.items():
                ctl[k] = min(ctl.get(k, float("inf")), v)
        print(json.dumps(row), flush=True)
    return prog, ctl


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_007)
    ap.add_argument("--more-seeds", default="",
                    help="comma-separated seeds read after the others")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.require_devices(cell["chips"])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    seeds += [int(s) for s in args.more_seeds.split(",") if s]
    prog, ctl = readings(cell, seeds, args.controls, args.seconds)
    print(json.dumps({"workload": args.workload, "lower": prog,
                      "upper": ctl}), flush=True)


if __name__ == "__main__":
    main()
