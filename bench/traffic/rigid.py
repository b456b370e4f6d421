"""Rigid-job streams for the benchmark, built from the seed alone.

The shape of Lublin and Feitelson, "The workload on parallel
supercomputers: modeling the characteristics of rigid jobs" (JPDC
63(11), 2003), vectorised, with the parameters of the mix file
(``bench/traffic/campaign-rigid.json``, ``"shape"``):

- width, in cores: serial with probability ``serial``; otherwise ``u``
  is uniform on ``[ulo, uhi - umed_gap]`` with probability ``p_lo``, else
  on ``[uhi - umed_gap, uhi]``, and the width is ``2**round(u)`` with
  probability ``p_pow2``, else ``round(2**u)``.  ``uhi = log2(max_cores)``;
- runtime, in seconds: ``ln(runtime)`` is drawn from ``Gamma(a1, b1)``
  with probability ``p``, else from ``Gamma(a2, b2)``, where
  ``p = clip(pa - pb * ref_nodes * cores / max_cores, 0, 1)`` couples
  long runtimes to wide jobs; runtimes are capped at ``cap_s``;
- arrivals: the diurnal process of ``streams.diurnal_arrivals``.

A job's class is its (width, runtime) bin under the configuration's
frozen ``edges``; its per-job columns are its runtime over its class's
reference runtime (``T_true[class, ref]``) and its node count on each
system, ``ceil(cores / cores_per_node)``.
"""

from __future__ import annotations

import numpy as np

from bench.traffic.streams import diurnal_arrivals


def shape_draw(rng, n: int, shape: dict):
    """(cores [n] int64, runtime [n] float64 seconds) of ``n`` rigid
    jobs."""
    uhi = np.log2(shape["max_cores"])
    cut = uhi - shape["umed_gap"]
    serial = rng.uniform(size=n) < shape["serial"]
    lo = rng.uniform(size=n) < shape["p_lo"]
    u = np.where(lo, rng.uniform(shape["ulo"], cut, n),
                 rng.uniform(cut, uhi, n))
    pow2 = rng.uniform(size=n) < shape["p_pow2"]
    cores = np.where(pow2, 2.0 ** np.round(u), np.round(2.0 ** u))
    cores = np.where(serial, 1.0, cores).astype(np.int64)
    p = np.clip(shape["pa"] - shape["pb"] * shape["ref_nodes"] * cores
                / shape["max_cores"], 0.0, 1.0)
    first = rng.uniform(size=n) < p
    log_t = np.where(first, rng.gamma(shape["a1"], shape["b1"], n),
                     rng.gamma(shape["a2"], shape["b2"], n))
    runtime = np.minimum(np.exp(log_t), shape["cap_s"])
    return cores, runtime


def classes(cores, runtime, edges: dict) -> np.ndarray:
    """Class index of each job: its width bin times the runtime bins plus
    its runtime bin (right-closed bins, as the program's trace replay
    bins)."""
    nt = len(edges["runtime_s"]) + 1
    return (np.searchsorted(edges["cores"], cores, side="right") * nt
            + np.searchsorted(edges["runtime_s"], runtime, side="right"))


def job_columns(cores, runtime, cls, tables: dict):
    """(t_scale [n] float32, n_job [n, S] int32) of each job."""
    T = np.asarray(tables["T_true"], np.float64)
    cpn = np.asarray(tables["cores_per_node"], np.float64)
    n_nodes = np.asarray(tables["n_nodes"], np.float64)
    t_scale = runtime / T[cls, tables["reference_system"]]
    n_job = np.clip(np.ceil(cores[:, None] / cpn[None, :]), 1.0,
                    n_nodes[None, :])
    return t_scale.astype(np.float32), n_job.astype(np.int32)


def rigid_stream(rng, n: int, traffic: dict, tables: dict):
    """One seeded stream: (prog [n] int32, arrival [n] float32,
    t_scale [n] float32, n_job [n, S] int32, cores [n], runtime [n])."""
    a = traffic["arrivals"]
    arrival = diurnal_arrivals(rng, n, a["rate_per_s"], a["base"],
                               a["peak"], a["period_s"])
    cores, runtime = shape_draw(rng, n, traffic["shape"])
    cls = classes(cores, runtime, tables["edges"])
    t_scale, n_job = job_columns(cores, runtime, cls, tables)
    return (cls.astype(np.int32), arrival, t_scale, n_job, cores, runtime)
