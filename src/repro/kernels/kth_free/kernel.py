"""Kth-free-time radix-select kernel in Pallas.

The scheduler's inner loop asks, for every system s, for the time at which
n_req[s] nodes are simultaneously free: the n_req[s]-th smallest entry of
the node-free row.  A full ``jnp.sort`` per simulation step is
O(S·maxN·log maxN) and serializes badly; instead we radix-select the kth
smallest directly: map f32 free-times to order-preserving uint32 keys and
walk the 32 bits MSB->LSB, at each bit counting candidates whose bit is 0
and descending into the half that contains rank k.  32 counting passes over
the [S, maxN] tile — O(S·maxN) work, fully vectorized over both axes (VPU
lanes hold nodes, sublanes hold systems), and bit-exact against the sort
reference because the selected value is an element of the input, not an
approximation.

Single-block kernel (no grid): the node matrix of any realistic SCC fits
VMEM many times over ([S, maxN] is a few KB); the win is replacing the sort
network with 32 compare-and-count sweeps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _f32_to_ordered_u32(x):
    """Order-preserving bijection f32 -> uint32 (IEEE-754 trick: flip sign
    bit for positives, flip all bits for negatives)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = (b >> 31).astype(jnp.bool_)
    return jnp.where(sign, ~b, b | jnp.uint32(0x80000000))


def _ordered_u32_to_f32(u):
    sign = (u >> 31).astype(jnp.bool_)
    b = jnp.where(sign, u & jnp.uint32(0x7FFFFFFF), ~u)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def radix_select_kth(node_free, n_req):
    """Pure-jnp radix select (the kernel's algorithm, usable on any backend
    and inside scan/vmap).  node_free: [S, maxN] f32; n_req: [S] int.
    Returns [S] f32: the n_req-th smallest per row (1-indexed, clipped)."""
    S, N = node_free.shape
    u = _f32_to_ordered_u32(node_free)                      # [S, N]
    k0 = jnp.clip(n_req, 1, N).astype(jnp.int32)            # [S]

    def bit_step(i, carry):
        active, k, val = carry
        shift = jnp.uint32(31) - i.astype(jnp.uint32)
        bit = ((u >> shift) & jnp.uint32(1)).astype(jnp.int32)   # [S, N]
        zeros = jnp.sum(active * (1 - bit), axis=1)              # [S]
        go_one = k > zeros                                       # [S]
        val = val | jnp.where(go_one, jnp.uint32(1) << shift, jnp.uint32(0))
        keep_bit = go_one.astype(jnp.int32)[:, None]             # [S, 1]
        active = active * (bit == keep_bit).astype(jnp.int32)
        k = jnp.where(go_one, k - zeros, k)
        return active, k, val

    active0 = jnp.ones((S, N), jnp.int32)
    val0 = jnp.zeros((S,), jnp.uint32)
    _, _, val = jax.lax.fori_loop(0, 32, bit_step, (active0, k0, val0))
    return _ordered_u32_to_f32(val)


def radix_select_kth_batched(node_free, n_req):
    """Batched radix select over a leading candidate axis (the EASY
    window's W tentative allocations per step are independent, so one
    vectorized call replaces W sequential selects).  node_free:
    [W, S, maxN] f32; n_req: [W, S] int.  Returns [W, S] f32, bit-exact
    per slice against ``radix_select_kth`` (the bit walk is integer
    counting — vmap only adds a leading axis to the counts)."""
    return jax.vmap(radix_select_kth)(node_free, n_req)


def _kth_free_kernel(free_ref, nreq_ref, out_ref):
    out_ref[...] = radix_select_kth(free_ref[...], nreq_ref[:, 0])[:, None]


def kth_free_pallas(node_free, n_req, *, interpret: bool = True):
    """node_free: [S, maxN] f32; n_req: [S] int32.  Returns [S] f32.

    Every block's last two dimensions equal the array's, the one form
    Mosaic accepts for S and maxN off the (8, 128) tile.  The output is
    therefore the 2-D column [S, 1], sliced back here: a 1-D (S,) block
    compiles alone but not under ``vmap`` (the campaign grid, the session
    pool), where it becomes (Squeezed, S) on an [L, S] array."""
    S, _ = node_free.shape
    return pl.pallas_call(
        _kth_free_kernel,
        in_specs=[pl.BlockSpec(node_free.shape, lambda: (0, 0)),
                  pl.BlockSpec((S, 1), lambda: (0, 0))],
        out_specs=pl.BlockSpec((S, 1), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, 1), jnp.float32),
        interpret=interpret,
    )(node_free.astype(jnp.float32), n_req.astype(jnp.int32)[:, None])[:, 0]


def _kth_free_kernel_batched(free_ref, nreq_ref, out_ref):
    out_ref[0] = radix_select_kth(free_ref[0], nreq_ref[0, :, 0])[:, None]


def kth_free_pallas_batched(node_free, n_req, *, interpret: bool = True):
    """Pallas twin of ``radix_select_kth_batched``: the grid runs one
    program instance per candidate, each radix-selecting its own [S, maxN]
    block.  node_free: [W, S, maxN] f32; n_req: [W, S] int32.  Returns
    [W, S] f32.  The output is [W, S, 1] in (1, S, 1) blocks, sliced back
    here, for the tiling reason ``kth_free_pallas`` gives."""
    W, S, N = node_free.shape
    return pl.pallas_call(
        _kth_free_kernel_batched,
        grid=(W,),
        in_specs=[pl.BlockSpec((1, S, N), lambda w: (w, 0, 0)),
                  pl.BlockSpec((1, S, 1), lambda w: (w, 0, 0))],
        out_specs=pl.BlockSpec((1, S, 1), lambda w: (w, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((W, S, 1), jnp.float32),
        interpret=interpret,
    )(node_free.astype(jnp.float32),
      n_req.astype(jnp.int32)[..., None])[..., 0]
