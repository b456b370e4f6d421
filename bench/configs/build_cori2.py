#!/usr/bin/env python3
"""Build ``bench/configs/cori2.json``: NERSC Cori's two partitions and the
frozen per-class tables of the rigid-job stream.

    python3 bench/configs/build_cori2.py

Draws ``DRAW`` jobs of the mix's shape (``bench/traffic/campaign-rigid.json``)
from ``SEED``, bins them 4 x 4 by the quantiles of width and runtime (the
program's trace-replay classes), and takes each class's tables from the
program's calibrated phase model (``workload_from_arrays(...,
calibrate=True)``).  The class edges are frozen with the tables, so every
run's stream is classed the same way.  Prints the offered-load arithmetic:
the mean node-seconds of a job on its fastest system, and the mean arrival
rate at which the stream offers ``offered_load`` of the facility.
"""

from __future__ import annotations

import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.traffic import rigid, streams  # noqa: E402

SEED = 1_600_000_003
DRAW = 1_000_000


def main():
    from repro.core.systems import CORI_SYSTEMS
    from repro.data.scenarios import workload_from_arrays
    mix = json.loads((ROOT / "bench/traffic/campaign-rigid.json").read_text())
    cores, runtime = rigid.shape_draw(streams.rng_for(SEED), DRAW,
                                      mix["shape"])
    w = workload_from_arrays(np.zeros(DRAW), runtime, cores, CORI_SYSTEMS,
                             n_size_bins=4, n_time_bins=4, calibrate=True)
    q = np.linspace(0, 1, 5)[1:-1]
    edges = {"cores": np.quantile(cores, q).tolist(),
             "runtime_s": np.quantile(runtime, q).tolist()}
    theta = [s.peak_flops_node * s.efficiency * s.cores_per_node
             for s in CORI_SYSTEMS]
    ids = [int(p[len("class"):]) for p in w.programs]
    assert ids == list(range(16)), ids      # every class is drawn
    tables = {
        "programs": list(w.programs), "systems": list(w.systems),
        "n_nodes": [int(x) for x in w.n_nodes],
        "cores_per_node": [s.cores_per_node for s in CORI_SYSTEMS],
        "idle_w": [float(x) for x in w.idle_w],
        "reference_system": int(np.argmax(theta)),
        "edges": edges,
        "n_req": np.asarray(w.n_req).tolist(),
        **{k: np.asarray(getattr(w, k), np.float64).tolist()
           for k in ("T_true", "E_true", "C_true", "T_comp", "E_comp")},
    }
    cls = rigid.classes(cores, runtime, edges)
    assert (cls == w.prog).all()
    t_scale, n_job = rigid.job_columns(cores, runtime, cls, tables)
    T = np.asarray(tables["T_true"])
    fast = np.argmin(T[cls], axis=1)
    ns = (n_job[np.arange(DRAW), fast] * T[cls, fast]
          * t_scale.astype(np.float64))
    nodes = float(sum(tables["n_nodes"]))
    rate = mix["offered_load"] * nodes / ns.mean()
    cfg = {
        "name": "cori2",
        "source": ("NERSC Cori (Cray XC40) documented configuration: 2,388 "
                   "Haswell nodes (2x E5-2698 v3, 32 c) + 9,688 KNL nodes "
                   "(Xeon Phi 7250, 68 c); job shape: Lublin & Feitelson, "
                   "JPDC 63(11) 2003"),
        "deployment": ("the whole facility: 2 partitions, 12,076 nodes, "
                       "[S, maxN] = [2, 9688]; every lane's node table "
                       "and learned profile lives on one chip"),
        "policy": {"name": "paper", "warm_start": True},
        "queue": "fcfs",
        "power_cap_w": None,
        "precision": ("float32: the engine's node tables, clocks and totals "
                      "(x64 is off)"),
        "guarantees": {"campaign": (
            "totals only: the energy, makespan, busy node-seconds and idle "
            "energy of the paper rule's FCFS schedule of the whole stream, "
            "each job at its own runtime and width, and the share of jobs "
            "placed as that schedule places them")},
        "reduced": [],
        "assumed": [
            "the power split (idle_w, cpu_w, net_w, disk_w) and "
            "efficiency/scalar_eff of core/systems.py CORI_HASWELL (from "
            "public TDPs, as BROADWELL's) and CORI_KNL (KNL's: the same "
            "architecture)",
            "network (10 GB/s a node) and file-system (2 GB/s a node) "
            "bandwidths",
            "class edges: the 4 x 4 quantiles of width and runtime of the "
            f"seed-{SEED} draw of {DRAW:,} jobs (tables.edges)",
            "every parameter of the stream's shape (the mix's 'shape'), "
            "among them the runtime-width coupling p = clip(0.78 - 0.0054 "
            "* 128 * cores / 76,416, 0, 1), the model's 128-node fit "
            "rescaled to this machine's share",
            "T_true/E_true/C_true/T_comp/E_comp taken once from the repo's "
            "calibrated phase model (workload_from_arrays(..., "
            "calibrate=True), phase fractions 0.7/0.2/0.1) and frozen here",
            "phase-model predictions per class equal the class truth "
            "(T_pred = T_true, C_pred = C_true); each job's own runtime "
            "and width differ from its class's, as a user's estimate does",
        ],
        "draw": {"seed": SEED, "jobs": DRAW,
                 "generator": "bench/traffic/rigid.py shape_draw"},
        "offered_load": {"load": mix["offered_load"],
                         "mean_node_s_fastest": float(ns.mean()),
                         "facility_nodes": nodes,
                         "rate_per_s": rate},
        "tables": tables,
    }
    out = ROOT / "bench/configs/cori2.json"
    out.write_text(json.dumps(cfg, indent=1) + "\n")
    print(json.dumps({"mean_node_s_fastest": float(ns.mean()),
                      "facility_nodes": nodes, "rate_per_s": rate,
                      "fastest_share": np.bincount(fast, minlength=2)
                      .tolist(), "pow2_share": float(
                          np.mean((cores & (cores - 1)) == 0)),
                      "edges": edges}))


if __name__ == "__main__":
    main()
