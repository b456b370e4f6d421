"""Manual hierarchical data-parallel trainer with compressed cross-pod
gradients (DESIGN.md §7 distributed-optimization tricks).

pjit handles single-program SPMD; this driver makes the cross-pod boundary
EXPLICIT with shard_map so the DCN hop can be compressed:

  - grads are psum'd over the intra-pod 'data' axis in full precision
    (ICI is cheap);
  - the cross-pod reduction runs through int8 error-feedback compression
    (repro.optim.compression) — DCN bytes halve vs bf16, and the EF
    residual keeps convergence;
  - the optimizer step runs replicated (params identical on all shards).

Used by tests/test_dp_compressed.py on a (pod, data) host-device mesh; on
real multi-pod TPU fleets the same code runs with the pod axis mapped over
DCN-connected slices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.optim import (AdamWConfig, adamw_update, compressed_psum,
                         init_error_state)


def make_dp_train_step(loss_fn, mesh, ocfg: AdamWConfig,
                       compress_cross_pod: bool = True):
    """loss_fn(params, batch) -> scalar.  Returns
    train_step(params, opt_state, err_state, batch) with batch sharded
    over ('pod', 'data') on dim 0 and params/opt replicated."""

    def shard_fn(params, opt_state, err_state, batch):
        # per-shard gradient on the local microbatch
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # intra-pod reduction: full precision over ICI
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "data"), grads)
        loss = jax.lax.pmean(loss, "data")
        # cross-pod reduction: int8 error-feedback over DCN
        if compress_cross_pod:
            grads, err_state = compressed_psum(grads, err_state, "pod")
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "pod"), grads)
        loss = jax.lax.pmean(loss, "pod")
        new_params, new_opt, metrics = adamw_update(
            grads, opt_state, ocfg, jax.tree.leaves(params)[0].dtype)
        return new_params, new_opt, err_state, loss, metrics["grad_norm"]

    rep = P()            # params/opt/err replicated across the mesh
    mapped = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(rep, rep, rep, P(("pod", "data"))),
        out_specs=(rep, rep, rep, rep, rep), check_vma=False)
    return jax.jit(mapped)


def init_dp_state(params):
    from repro.optim import adamw_init
    return adamw_init(params), init_error_state(params)
