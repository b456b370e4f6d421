"""Seconds JAX spent lowering and compiling in this run (jax.monitoring):
the part of set-up that a warm compile cache takes away."""


def read(run):
    return run["compile_s"]
