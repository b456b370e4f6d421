"""Kth-free-time radix-select kernel in Pallas.

The scheduler's inner loop asks, for every system s, for the time at which
n_req[s] nodes are simultaneously free: the n_req[s]-th smallest entry of
the node-free row.  A full ``jnp.sort`` per simulation step is
O(S·maxN·log maxN) and serializes badly; instead we radix-select the kth
smallest directly: map f32 free-times to order-preserving int32 keys and
build the answer's 32 bits MSB->LSB, a threshold walk: at each bit, count
the keys below the decided prefix with that bit set, and keep the bit
while fewer than k are below.  32 counting passes over the [S, maxN]
tile, each a compare and a sum per node with no per-node state carried
between passes — O(S·maxN) work, fully vectorized over both axes (VPU
lanes hold nodes, sublanes hold systems), and bit-exact against the sort
reference because the selected value is an element of the input, not an
approximation.

Layout.  An unvmapped ``kth_free_pallas`` call is one block with no grid:
the node matrix of any realistic SCC fits VMEM many times over ([S, maxN]
is a few KB).  Under ``vmap`` (the campaign's lanes, each device's lanes
inside the grid ``shard_map``, a vmap nested in another) its
``custom_vmap`` rule folds every lane into one invocation of the
lane-folded kernel, an [L, S, maxN] block walked once for all L·S rows.
At the JSCC facility's width the 32 passes are bound by the latency of
each pass's cross-lane count, not by its width, so L lanes in one walk
cost about what one lane does, where a grid step per lane (Pallas's own
batching rule) would run L walks one after another; at ten thousand
nodes a row each pass is bound by its per-node work.  Where the lanes
outgrow ``LANE_BLOCK_BYTES`` the kernel grids over groups of as many
lanes as fit.
"""

from __future__ import annotations

from functools import cache, partial

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.extend.core import Primitive
from jax.interpreters import mlir

from repro import obs

#: node-free bytes of one lane-folded block.  The walk keeps about five
#: block-sized arrays in VMEM (the input's two buffers, the keys and a
#: pass's compare plane): a 3.9 MB block of 24 lanes of [4, 10240] asks
#: v5e's compiler for 18.9 MB of its 16 MiB of scoped VMEM, so a block
#: stays at 1 MiB, with room to spare.
LANE_BLOCK_BYTES = 1 << 20


def _flip_negatives(b):
    """int32 bits -> the same bits with a negative's magnitude bits flipped.
    Its own inverse: the sign bit, which decides the flip, is kept."""
    return b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))


def _f32_to_ordered_i32(x):
    """Order-preserving bijection f32 -> int32: the signed integer order of
    the keys is the float order (-0.0 below +0.0, a NaN beyond the
    infinity of its sign)."""
    return _flip_negatives(jax.lax.bitcast_convert_type(x, jnp.int32))


def _ordered_i32_to_f32(key):
    return jax.lax.bitcast_convert_type(_flip_negatives(key), jnp.float32)


def radix_select_kth(node_free, n_req):
    """Pure-jnp radix select (the kernel's algorithm, usable on any backend
    and inside scan/vmap).  node_free: [..., S, maxN] f32; n_req: [..., S]
    int, or the column [..., S, 1] (the lane-folded kernel's form, which
    keeps every count in the layout of its row).  Returns n_req's shape in
    f32: the n_req-th smallest per row (1-indexed, clipped).

    A threshold walk: the k-th smallest key is the largest v with fewer
    than k keys below it, built bit by bit from the most significant.
    ``val`` is the decided prefix with the bits below it zero, read as an
    unsigned number and held in signed form (so ``INT_MIN`` is the empty
    prefix and setting a bit is an XOR; the top bit clears the sign).
    Each pass tries the next bit, ``t = val ^ bit``, counts the keys below
    t and keeps t where that count stays under k.  Only the prefix is
    carried through the 32 passes; nothing is stored per node.  The result
    is the k-th smallest input element itself, so it is bit-exact."""
    N = node_free.shape[-1]
    col = n_req.ndim == node_free.ndim
    key = _f32_to_ordered_i32(node_free)                    # [..., S, N]
    k = jnp.clip(n_req, 1, N).astype(jnp.int32)             # [..., S(, 1)]

    def bit_step(i, val):
        t = val ^ (jnp.int32(1) << (31 - i))               # as k
        below = key < (t if col else t[..., None])           # [..., S, N]
        c = jnp.sum(below.astype(jnp.int32), axis=-1, keepdims=col)
        return jnp.where(c < k, t, val)

    val0 = jnp.full(k.shape, jnp.iinfo(jnp.int32).min, jnp.int32)
    val = jax.lax.fori_loop(0, 32, bit_step, val0)
    return _ordered_i32_to_f32(val)


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


def _kth_free_block(node_free, n_req, interpret):
    """One [S, maxN] table in one block, no grid.  Every block's last two
    dimensions equal the array's, the one form Mosaic accepts for S and
    maxN off the (8, 128) tile.  The output is therefore the 2-D column
    [S, 1], sliced back here."""
    S, _ = node_free.shape
    return pl.pallas_call(
        _kth_free_kernel,
        in_specs=[pl.BlockSpec(node_free.shape, lambda: (0, 0)),
                  pl.BlockSpec((S, 1), lambda: (0, 0))],
        out_specs=pl.BlockSpec((S, 1), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, 1), jnp.float32),
        interpret=interpret,
    )(node_free, n_req[:, None])[:, 0]


def _kth_free_kernel_lanes(free_ref, nreq_ref, out_ref):
    out_ref[...] = radix_select_kth(free_ref[...], nreq_ref[...])


def _lane_groups(L, S, N):
    """(lanes G of one block, grid steps) of the lane-folded kernel over
    L lanes of [S, N]: G is the most lanes that fit ``LANE_BLOCK_BYTES``
    (at least one), and the grid has one step per group."""
    G = min(L, max(1, LANE_BLOCK_BYTES // (S * N * 4)))
    return G, pl.cdiv(L, G)


# The lane-folded kernel's result passes through ``_noted_p``, which is
# nothing in the compiled program: lowering it notes the fold in
# ``repro.obs.kth_free_calls()``.  A note at trace time would also count
# the folds an outer vmap's rule replaces before anything is lowered.
_noted_p = Primitive("kth_free_noted")
_noted_p.def_abstract_eval(lambda x, **_: x)
_noted_p.def_impl(lambda x, **fold: jax.jit(partial(_noted_p.bind, **fold))(x))


def _noted_lowering(ctx, x, **fold):
    obs.note_kth_free(**fold)
    return [x]


mlir.register_lowering(_noted_p, _noted_lowering)


def _kth_free_lanes(node_free, n_req, interpret):
    """The lane-folded kernel: node_free [L, S, maxN] f32, n_req [L, S]
    int32 -> [L, S] f32, one walk over the L·S rows of a block.  A last
    group that runs past L reads lanes it never writes back."""
    L, S, N = node_free.shape
    G, steps = _lane_groups(L, S, N)
    out = pl.pallas_call(
        _kth_free_kernel_lanes,
        grid=(steps,),
        in_specs=[pl.BlockSpec((G, S, N), lambda g: (g, 0, 0)),
                  pl.BlockSpec((G, S, 1), lambda g: (g, 0, 0))],
        out_specs=pl.BlockSpec((G, S, 1), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, S, 1), jnp.float32),
        interpret=interpret,
        name="kth_free_time",
    )(node_free, n_req[..., None])[..., 0]
    return _noted_p.bind(out, lanes=L, grid_steps=steps,
                         block_bytes=G * S * N * 4)


@cache
def _kth_free_op(interpret: bool):
    """``kth_free_pallas``'s operation, [..., S, maxN] -> [..., S]: one
    table goes to ``_kth_free_block``, a stack of them to
    ``_kth_free_lanes``.  Its vmap rule adds the mapped axis to the stack
    (broadcasting an argument that is not mapped), so a vmap at any depth
    folds into the same single invocation."""
    @custom_vmap
    def op(node_free, n_req):
        if node_free.ndim == 2:
            return _kth_free_block(node_free, n_req, interpret)
        lead, (S, N) = node_free.shape[:-2], node_free.shape[-2:]
        out = _kth_free_lanes(node_free.reshape(-1, S, N),
                              n_req.reshape(-1, S), interpret)
        return out.reshape(*lead, S)

    @op.def_vmap
    def _(axis_size, in_batched, node_free, n_req):
        node_free, n_req = (
            x if b else jnp.broadcast_to(x, (axis_size, *x.shape))
            for x, b in zip((node_free, n_req), in_batched))
        return op(node_free, n_req), True

    return op


def kth_free_pallas(node_free, n_req, *, interpret: bool = True):
    """node_free: [S, maxN] f32; n_req: [S] int32.  Returns [S] f32.

    Alone: one block, no grid.  Under ``vmap`` at any depth: the
    lane-folded kernel, one invocation for all the mapped lanes (see the
    module docstring)."""
    return _kth_free_op(interpret)(node_free.astype(jnp.float32),
                                   n_req.astype(jnp.int32))


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
