"""What the references share: the facility tables, rounding, the fault
draws, and the comparison of a result with its reference."""

from __future__ import annotations

import numpy as np


def same(x):
    return x


def bf16(x):
    """Round to the nearest bfloat16 (the control's precision)."""
    import ml_dtypes
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def facility(tables: dict) -> dict:
    """The frozen tables as float64 Python lists (fast scalar access)."""
    T = np.asarray(tables["T_true"], np.float64)
    E = np.asarray(tables["E_true"], np.float64)
    return {"S": T.shape[1], "n_nodes": list(tables["n_nodes"]),
            "n_req": np.asarray(tables["n_req"], np.int64).tolist(),
            "T": T.tolist(), "C": np.asarray(tables["C_true"]).tolist(),
            "E": E.tolist(), "w_pow": (E / T).tolist(),
            "idle_w": list(map(float, tables["idle_w"]))}


def straggler_factors(seed: int, J: int, prob: float, factor: float):
    """Per-job runtime factor of the configuration's fault model: job ``j``
    straggles when the first of two uniforms drawn from
    ``fold_in(split(key(seed))[1], j)`` is below ``prob``.  Drawn on the
    host's CPU device, so the chip's memory is not touched."""
    if prob <= 0.0:
        return np.ones(J)
    import jax
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        key = jax.random.split(jax.random.key(int(seed)))[1]
        u = jax.jit(jax.vmap(
            lambda j: jax.random.uniform(jax.random.fold_in(key, j),
                                         (2,))[0]))(np.arange(J))
    return np.where(np.asarray(u) < prob, factor, 1.0)


def idle_energy(tables: dict, makespan, busy) -> np.ndarray:
    """Idle draw of each lane: every node idles until the makespan except
    while busy (``makespan`` [L], ``busy`` [L, S] node-seconds)."""
    idle = np.asarray(tables["idle_w"], np.float64)
    n = np.asarray(tables["n_nodes"], np.float64)
    return (np.sum(idle * n) * np.asarray(makespan, np.float64)
            - np.asarray(busy, np.float64) @ idle)


def rel_gap(got, ref) -> float:
    """Largest |got - ref| / |ref| over the elements (|ref| floored at
    1e-30 so that an exact zero compares exactly)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1e-30)))


def placement_gap(runs_got, runs_ref, n_jobs: int) -> float:
    """Largest share over the lanes (leading axis) of the jobs counted on
    another (program, system) cell of the learned run counts: 0 when
    every job went where the reference put it."""
    d = np.abs(np.asarray(runs_got, np.int64) - np.asarray(runs_ref,
                                                           np.int64))
    return float(d.reshape(d.shape[0], -1).sum(axis=1).max()) / (2.0 * n_jobs)
