"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle
(deliverable c: every kernel sweeps shapes/dtypes against ref.py)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_bhsd, attention_ref
from repro.kernels.ep import ep_pairs_pallas, ep_pairs_ref
from repro.kernels.is_hist import key_histogram_pallas, key_histogram_ref
from repro.kernels.stencil3d import stencil7_pallas, stencil7_ref


# ----------------------------------------------------------- flash attention

@pytest.mark.parametrize("b,sq,sk,h,kv,hd,bq,bk", [
    (2, 256, 256, 8, 2, 64, 128, 128),
    (1, 256, 256, 4, 4, 128, 64, 128),
    (2, 128, 384, 4, 1, 64, 128, 128),     # MQA, rectangular
    (1, 512, 512, 2, 2, 32, 128, 256),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, sq, sk, h, kv, hd, bq, bk, causal):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, sk, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, sk, kv, hd), jnp.float32)
    out = flash_attention_bhsd(q, k, v, causal=causal, block_q=bq, block_k=bk,
                               interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=3e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-5), (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, atol):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), dtype)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), dtype)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), dtype)
    out = flash_attention_bhsd(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    assert out.dtype == dtype
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=atol)


# ----------------------------------------------------------------------- EP

@pytest.mark.parametrize("n,block", [(4096, 1024), (8192, 2048), (2048, 2048)])
def test_ep_kernel_sweep(n, block):
    u = jax.random.uniform(jax.random.key(2), (2, n), minval=-1.0, maxval=1.0)
    h1, s1 = ep_pairs_pallas(u, block_n=block, interpret=True)
    h2, s2 = ep_pairs_ref(u)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_allclose(s1, s2, rtol=1e-4)
    # acceptance ratio sanity (pi/4 for uniform pairs on the square)
    assert abs(float(h1.sum()) / n - np.pi / 4) < 0.05


# ----------------------------------------------------------------------- IS

@pytest.mark.parametrize("n,buckets,shift,block", [
    (8192, 64, 8, 2048),
    (16384, 256, 6, 4096),
    (4096, 16, 10, 4096),
])
def test_is_histogram_sweep(n, buckets, shift, block):
    keys = jax.random.randint(jax.random.key(3), (n,), 0,
                              buckets << shift, jnp.int32)
    h1 = key_histogram_pallas(keys, n_buckets=buckets, bucket_shift=shift,
                              block_n=block, interpret=True)
    h2 = key_histogram_ref(keys, n_buckets=buckets, bucket_shift=shift)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    assert int(h1.sum()) == n


# ------------------------------------------------------------------ stencil

@pytest.mark.parametrize("nx,ny,nz,bx", [
    (32, 16, 16, 8), (64, 32, 32, 16), (16, 16, 16, 16), (48, 8, 8, 8),
])
def test_stencil_sweep(nx, ny, nz, bx):
    u = jax.random.normal(jax.random.key(4), (nx, ny, nz), jnp.float32)
    o1 = stencil7_pallas(u, bx=bx, interpret=True)
    o2 = stencil7_ref(u)
    np.testing.assert_allclose(o1, o2, atol=2e-5)


def test_stencil_boundary_is_dirichlet_zero():
    """Global-edge neighbours must contribute zero (not wrap / clamp)."""
    u = jnp.ones((16, 8, 8), jnp.float32)
    out = stencil7_pallas(u, bx=8, interpret=True)
    ref = stencil7_ref(u)
    np.testing.assert_allclose(out, ref, atol=1e-6)
    # interior point: -6 + 6 = 0; corner point: -6 + 3 = -3
    assert float(out[8, 4, 4]) == pytest.approx(0.0, abs=1e-5)
    assert float(out[0, 0, 0]) == pytest.approx(-3.0, abs=1e-5)


# ---------------------------------------------------------------- kth free

from repro.kernels.kth_free import (kth_free_ref, kth_free_pallas,  # noqa: E402
                                    kth_free_batched_ref,
                                    kth_free_pallas_batched,
                                    kth_free_time, kth_free_time_batched,
                                    kth_free_time_shared,
                                    radix_select_kth,
                                    radix_select_kth_batched)
from repro.kernels.kth_free.kernel import LANE_BLOCK_BYTES  # noqa: E402


@pytest.mark.parametrize("s,n,seed", [
    (4, 136, 0),      # the JSCC node matrix
    (2, 8, 1),
    (7, 200, 2),
    (3, 129, 3),      # non-multiple-of-lane width
    (2, 9688, 4),     # NERSC Cori's two partitions
])
def test_kth_free_sweep(s, n, seed):
    rng = np.random.default_rng(seed)
    free = rng.uniform(0, 1e6, (s, n)).astype(np.float32)
    free[rng.random((s, n)) < 0.3] = 1e30
    free[rng.random((s, n)) < 0.3] = 0.0
    nreq = rng.integers(1, n + 1, s).astype(np.int32)
    ref = np.asarray(kth_free_ref(jnp.asarray(free), jnp.asarray(nreq)))
    pal = np.asarray(kth_free_pallas(jnp.asarray(free), jnp.asarray(nreq),
                                     interpret=True))
    sel = np.asarray(radix_select_kth(jnp.asarray(free), jnp.asarray(nreq)))
    np.testing.assert_array_equal(ref, pal)
    np.testing.assert_array_equal(ref, sel)


def test_kth_free_clips_out_of_range_requests():
    free = jnp.asarray(np.arange(12, dtype=np.float32).reshape(2, 6))
    nreq = jnp.asarray(np.array([0, 99], np.int32))   # clipped to [1, N]
    out = np.asarray(radix_select_kth(free, nreq))
    np.testing.assert_array_equal(out, [0.0, 11.0])


def _batched_case(wn, s, n, seed, sentinel_row=True):
    """Random [W, S, maxN] free-time stack with BIG sentinels, idle ties,
    and (optionally) one all-sentinel padding row."""
    rng = np.random.default_rng(seed)
    free = rng.uniform(0, 1e6, (wn, s, n)).astype(np.float32)
    free[rng.random((wn, s, n)) < 0.3] = 1e30
    free[rng.random((wn, s, n)) < 0.3] = 0.0
    if sentinel_row:
        free[0, 0, :] = 1e30               # a fully-padded (nonexistent) row
    nreq = rng.integers(1, n + 1, (wn, s)).astype(np.int32)
    return jnp.asarray(free), jnp.asarray(nreq)


@pytest.mark.parametrize("wn,s,n,seed", [
    (1, 4, 136, 0),       # W=1 degenerate (window=0 candidate batch)
    (9, 4, 136, 1),       # the JSCC node matrix, default window + head
    (17, 3, 129, 2),      # W=16 window, non-multiple-of-lane width
    (5, 2, 8, 3),
    (33, 7, 200, 4),      # W=32 window, wide stack
])
def test_kth_free_batched_sweep(wn, s, n, seed):
    """Batched radix + batched Pallas vs the vmapped jnp.sort oracle,
    bit for bit, across candidate-count/system/node shapes."""
    free, nreq = _batched_case(wn, s, n, seed)
    ref = np.asarray(kth_free_batched_ref(free, nreq))
    sel = np.asarray(radix_select_kth_batched(free, nreq))
    pal = np.asarray(kth_free_pallas_batched(free, nreq, interpret=True))
    np.testing.assert_array_equal(ref, sel)
    np.testing.assert_array_equal(ref, pal)


def test_kth_free_batched_matches_unbatched_per_slice():
    """The batched entry point is exactly W unbatched calls."""
    free, nreq = _batched_case(6, 4, 64, 5)
    out = np.asarray(kth_free_time_batched(free, nreq, force="jnp"))
    for wi in range(6):
        np.testing.assert_array_equal(
            out[wi], np.asarray(kth_free_time(free[wi], nreq[wi],
                                              force="jnp")))


@pytest.mark.parametrize("force", ["jnp", "sort", "pallas_interpret"])
def test_kth_free_batched_dispatch_modes_agree(force):
    free, nreq = _batched_case(8, 4, 136, 6)
    ref = np.asarray(kth_free_batched_ref(free, nreq))
    np.testing.assert_array_equal(
        ref, np.asarray(kth_free_time_batched(free, nreq, force=force)))


@pytest.mark.parametrize("wn", [1, 8, 17])
@pytest.mark.parametrize("force", [None, "jnp", "sort", "pallas_interpret"])
def test_kth_free_shared_bit_exact(wn, force):
    """Shared-table entry (one sort serves all W candidates) vs the
    broadcast batched oracle, every dispatch mode, including the W=1
    degenerate batch and an all-sentinel padding row."""
    rng = np.random.default_rng(40 + wn)
    free = rng.uniform(0, 1e6, (4, 136)).astype(np.float32)
    free[rng.random((4, 136)) < 0.3] = 1e30
    free[rng.random((4, 136)) < 0.3] = 0.0
    free[2, :] = 1e30                      # all-sentinel system row
    nreq = rng.integers(1, 137, (wn, 4)).astype(np.int32)
    free, nreq = jnp.asarray(free), jnp.asarray(nreq)
    ref = np.asarray(kth_free_batched_ref(
        jnp.broadcast_to(free, (wn,) + free.shape), nreq))
    np.testing.assert_array_equal(
        ref, np.asarray(kth_free_time_shared(free, nreq, force=force)))


def test_kth_free_shared_clips_out_of_range_requests():
    free = jnp.asarray(np.arange(12, dtype=np.float32).reshape(2, 6))
    nreq = jnp.asarray(np.array([[0, 99], [1, 6]], np.int32))
    out = np.asarray(kth_free_time_shared(free, nreq))
    np.testing.assert_array_equal(out, [[0.0, 11.0], [0.0, 11.0]])


from repro.kernels.kth_free import kth_free_time_rows  # noqa: E402


def _rows_oracle(table, sels, nreq):
    """Reservation recheck the slow way: per reservation, one scalar
    sort-and-index of its reserved system's row."""
    out = np.zeros(len(sels), np.float32)
    for e in range(len(sels)):
        row = np.sort(np.asarray(table[int(sels[e])]))
        out[e] = row[int(np.clip(nreq[e] - 1, 0, row.size - 1))]
    return out


@pytest.mark.parametrize("wn,s,n,seed", [
    (2, 4, 136, 0),       # W=1 conservative window (head + 1 slot)
    (9, 4, 136, 1),       # the JSCC node matrix, default window
    (17, 3, 129, 2),      # W=16, non-multiple-of-lane width
])
@pytest.mark.parametrize("force", [None, "sort", "jnp", "pallas_interpret"])
def test_kth_free_rows_bit_exact(wn, s, n, seed, force):
    """The [W] reservation recheck (ISSUE 5: one shared sort serves every
    pending reservation) vs the scalar sort-per-slot oracle, every
    dispatch mode, bit for bit — including repeated reserved systems,
    BIG sentinels and idle ties."""
    rng = np.random.default_rng(seed)
    free = rng.uniform(0, 1e6, (s, n)).astype(np.float32)
    free[rng.random((s, n)) < 0.3] = 1e30
    free[rng.random((s, n)) < 0.3] = 0.0
    free[0, :] = 1e30                      # an all-sentinel system row
    sels = rng.integers(0, s, wn).astype(np.int32)
    nreq = rng.integers(1, n + 1, wn).astype(np.int32)
    ref = _rows_oracle(free, sels, nreq)
    out = np.asarray(kth_free_time_rows(
        jnp.asarray(free), jnp.asarray(sels), jnp.asarray(nreq),
        force=force))
    np.testing.assert_array_equal(ref, out)


def test_kth_free_rows_clips_out_of_range_requests():
    table = jnp.asarray(np.arange(12, dtype=np.float32).reshape(2, 6))
    sels = jnp.asarray(np.array([0, 1, 0], np.int32))
    nreq = jnp.asarray(np.array([0, 99, 3], np.int32))
    out = np.asarray(kth_free_time_rows(table, sels, nreq))
    np.testing.assert_array_equal(out, [0.0, 11.0, 2.0])


# ------------------------------------------------- kth free, lanes folded

from repro import obs  # noqa: E402


def _lanes_case(b, n, seed, s=4):
    """[b, s, n] node-free tables with idle ties and BIG sentinels, and
    [b, s] requests that run past both ends of [1, n] (clipped)."""
    rng = np.random.default_rng(seed)
    free = rng.uniform(0, 1e6, (b, s, n)).astype(np.float32)
    free[rng.random((b, s, n)) < 0.3] = 1e30
    free[rng.random((b, s, n)) < 0.3] = 0.0
    nreq = rng.integers(-2, n + 3, (b, s)).astype(np.int32)
    return jnp.asarray(free), jnp.asarray(nreq)


def _pallas_interpret(free, nreq):
    return kth_free_time(free, nreq, force="pallas_interpret")


@pytest.mark.parametrize("n", [136, 10240])
@pytest.mark.parametrize("b", [1, 3, 12, 13])
@pytest.mark.parametrize("batched", [(True, False), (True, True),
                                     (False, True), (False, False)])
def test_kth_free_folded_bit_exact(batched, b, n):
    """vmap over lanes of the Pallas kth-free (interpret mode) vs the sort
    oracle, bit for bit, for each pattern of mapped arguments: the
    campaign's (tables mapped, the job's request shared), both mapped,
    one table asked for many requests, and neither (a vmap over another
    argument, which leaves the call unvmapped).  maxN 10240 takes more
    than one group of lanes from 12 lanes up."""
    free, nreq = _lanes_case(b, n, seed=100 * b + n % 97)
    tf, tn = batched
    f_ax, n_ax = (0 if tf else None), (0 if tn else None)
    free_in, nreq_in = (free if tf else free[0]), (nreq if tn else nreq[0])

    def lane(i, f, r):
        return _pallas_interpret(f, r) + 0.0 * i
    out = jax.jit(jax.vmap(lane, in_axes=(0, f_ax, n_ax)))(
        jnp.arange(b, dtype=jnp.float32), free_in, nreq_in)
    if tf or tn:
        ref = jax.vmap(kth_free_ref, in_axes=(f_ax, n_ax))(free_in, nreq_in)
    else:
        ref = jnp.broadcast_to(kth_free_ref(free_in, nreq_in), (b, 4))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_kth_free_folded_inside_scan_matches_sort():
    """The campaign's shape of call, vmap over lanes of a lax.scan whose
    step feeds the kth-free times back into the tables: every step's
    result and the final tables equal the sort placer's."""
    free, nreq = _lanes_case(12, 136, seed=7)

    def grid(force):
        def lane(c, r):
            def body(c, _):
                k = kth_free_time(c, r, force=force)
                return jnp.where(c <= k[:, None], k[:, None] + 1.0, c), k
            return jax.lax.scan(body, c, None, length=6)
        return jax.jit(jax.vmap(lane, in_axes=(0, None)))(free, nreq[0])
    for got, want in zip(jax.tree.leaves(grid("pallas_interpret")),
                         jax.tree.leaves(grid("sort"))):
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("inner_batched", [(True, True), (True, False)])
def test_kth_free_folded_nested_vmap(inner_batched):
    """Sessions x lanes: a vmap around the lanes' vmap folds both axes
    into one invocation of 5 x 3 lanes."""
    free, nreq = _lanes_case(15, 136, seed=11)
    free, nreq = free.reshape(5, 3, 4, 136), nreq.reshape(5, 3, 4)
    n_ax = 0 if inner_batched[1] else None
    nreq_in = nreq if inner_batched[1] else nreq[:, 0]
    jax.clear_caches()
    seen = len(obs.kth_free_calls())
    out = jax.vmap(jax.vmap(_pallas_interpret, in_axes=(0, n_ax)))(
        free, nreq_in)
    ref = jax.vmap(jax.vmap(kth_free_ref, in_axes=(0, n_ax)))(free, nreq_in)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert [c.lanes for c in obs.kth_free_calls()[seen:]] == [15]


def test_kth_free_calls_record_folded_lowerings_only():
    """An unvmapped call notes nothing; a vmapped one notes its lanes,
    its grid steps and its block's bytes."""
    jax.clear_caches()
    seen = len(obs.kth_free_calls())
    free, nreq = _lanes_case(7, 136, seed=3)
    np.testing.assert_array_equal(
        np.asarray(kth_free_ref(free[0], nreq[0])),
        np.asarray(_pallas_interpret(free[0], nreq[0])))
    assert obs.kth_free_calls()[seen:] == ()
    jax.vmap(_pallas_interpret)(free, nreq)
    assert obs.kth_free_calls()[seen:] == (
        obs.KthFreeCall(lanes=7, grid_steps=1, block_bytes=7 * 4 * 136 * 4),)
    big, nbig = _lanes_case(13, 10240, seed=4)
    jax.vmap(_pallas_interpret, in_axes=(0, None))(big, nbig[0])
    lanes = LANE_BLOCK_BYTES // (4 * 10240 * 4)
    assert obs.kth_free_calls()[seen + 1:] == (obs.KthFreeCall(
        lanes=13, grid_steps=-(-13 // lanes),
        block_bytes=lanes * 4 * 10240 * 4),)


# ------------------------------------------------ kth free, edge values

def _active_mask_walk(node_free, n_req):
    """A second, independent radix walk that compares bits where the sort
    oracle compares values: ordered uint32 keys, and at each bit from the
    most significant a count of the zeros among a row's active nodes, a
    descent into the half that holds rank k and a per-node active mask
    narrowed to it.  node_free [..., S, N] f32, n_req [..., S] int."""
    N = node_free.shape[-1]
    b = jax.lax.bitcast_convert_type(node_free, jnp.uint32)
    neg = (b >> 31).astype(jnp.bool_)
    u = jnp.where(neg, ~b, b | jnp.uint32(0x80000000))

    def bit_step(i, carry):
        active, k, val = carry
        shift = jnp.uint32(31) - i.astype(jnp.uint32)
        bit = ((u >> shift) & jnp.uint32(1)).astype(jnp.int32)
        zeros = jnp.sum(active * (1 - bit), axis=-1)
        go_one = k > zeros
        val = val | jnp.where(go_one, jnp.uint32(1) << shift, jnp.uint32(0))
        active = active * (bit == go_one[..., None]).astype(jnp.int32)
        return active, jnp.where(go_one, k - zeros, k), val

    k0 = jnp.clip(n_req, 1, N).astype(jnp.int32)
    _, _, val = jax.lax.fori_loop(
        0, 32, bit_step, (jnp.ones(node_free.shape, jnp.int32), k0,
                          jnp.zeros(k0.shape, jnp.uint32)))
    neg = (val >> 31).astype(jnp.bool_)
    b = jnp.where(neg, val & jnp.uint32(0x7FFFFFFF), ~val)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _edge_rows(kind, shape, seed):
    """[L, S, N] node-free tables of one kind of awkward value, and [L, S]
    requests that take 0, 1, N and N + 5 (clipped) besides values inside
    [1, N]."""
    rng = np.random.default_rng(seed)
    L, S, N = shape
    tiny = np.finfo(np.float32).tiny
    if kind == "ties":                       # few distinct values
        free = rng.integers(0, 5, shape).astype(np.float32) * 3600.0
    elif kind == "signed_zeros":
        free = rng.choice(np.float32([-0.0, 0.0, 1.0, -1.0]), shape)
    elif kind == "negatives":
        free = rng.uniform(-1e6, 1e5, shape).astype(np.float32)
    elif kind == "infinities":
        free = rng.choice(np.float32([-np.inf, np.inf, -1e30, 1e30, 0.0,
                                      7.5]), shape)
    elif kind == "denormals":
        free = rng.choice(np.float32([-0.0, 0.0, 1e-45, -1e-45, 1e-40,
                                      -1e-40, tiny, -tiny]), shape)
    elif kind == "all_equal":
        free = np.broadcast_to(rng.choice(np.float32(
            [-0.0, 0.0, 86400.0, np.inf, -2.5]), (L, S, 1)), shape).copy()
    else:                                    # "last_bit": ulp neighbours
        bits = np.float32(12345.678).view(np.int32) + rng.integers(
            -3, 4, shape)
        free = bits.astype(np.int32).view(np.float32)
        free[:, -1] = -free[:, -1]
    nreq = rng.integers(1, N + 1, (L, S))
    nreq = np.where(rng.random((L, S)) < 0.5,
                    rng.choice([0, 1, N, N + 5], (L, S)), nreq)
    nreq.reshape(-1)[:4] = [0, 1, N, N + 5]
    return jnp.asarray(free), jnp.asarray(nreq.astype(np.int32))


def _flushed(x):
    """Values with denormals as zeros: jnp.sort on XLA:CPU orders them so."""
    x = np.asarray(x)
    return np.where(np.abs(x) < np.finfo(np.float32).tiny, 0.0, x)


_EDGE_KINDS = ("ties", "signed_zeros", "negatives", "infinities",
               "denormals", "all_equal", "last_bit")


@pytest.mark.parametrize("kind", _EDGE_KINDS)
@pytest.mark.parametrize("force", ["pallas_interpret", "jnp"])
@pytest.mark.parametrize("shape", [(12, 4, 136), (12, 2, 9688)],
                         ids=["jscc4", "cori2"])
def test_kth_free_walk_edge_values_bit_exact(shape, force, kind):
    """The campaign's folded shapes under vmap, rows of awkward values:
    equal to the sort oracle by value and to the active-mask walk bit for
    bit (so -0.0 and +0.0, and denormals, are told apart)."""
    free, nreq = _edge_rows(kind, shape, seed=10 * shape[-1] + _EDGE_KINDS.index(kind))
    out = jax.jit(jax.vmap(partial(kth_free_time, force=force)))(free, nreq)
    out = np.asarray(out)
    ref = jax.vmap(kth_free_ref)(free, nreq)
    np.testing.assert_array_equal(_flushed(ref), _flushed(out))
    want = np.asarray(_active_mask_walk(free, nreq))
    np.testing.assert_array_equal(want.view(np.int32), out.view(np.int32))


# ---------------------------------------------------------------- SSD scan

from repro.kernels.ssd_scan import ssd_scan_pallas, ssd_scan_ref  # noqa: E402


@pytest.mark.parametrize("bh,l,p,n,rep,chunk", [
    (4, 128, 16, 8, 2, 32),
    (2, 64, 8, 16, 1, 16),
    (6, 96, 32, 8, 3, 32),
])
def test_ssd_scan_sweep(bh, l, p, n, rep, chunk):
    ks = jax.random.split(jax.random.key(5), 5)
    bg = bh // rep
    x = jax.random.normal(ks[0], (bh, l, p), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, l)))
    A = -jnp.exp(jax.random.normal(ks[2], (bh,)) * 0.3)
    dA = dt * A[:, None]
    B = jax.random.normal(ks[3], (bg, l, n), jnp.float32) * 0.5
    C = jax.random.normal(ks[4], (bg, l, n), jnp.float32) * 0.5
    y1, s1 = ssd_scan_pallas(x, dt, dA, B, C, chunk=chunk, interpret=True)
    y2, s2 = ssd_scan_ref(x, dt, dA, B, C, chunk=chunk)
    np.testing.assert_allclose(y1, y2, atol=2e-4)
    np.testing.assert_allclose(s1, s2, atol=2e-4)


def test_ssd_scan_chunk_invariance():
    ks = jax.random.split(jax.random.key(6), 5)
    bh, l, p, n = 2, 128, 8, 8
    x = jax.random.normal(ks[0], (bh, l, p), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bh, l)))
    dA = dt * -0.5
    B = jax.random.normal(ks[3], (bh, l, n), jnp.float32) * 0.5
    C = jax.random.normal(ks[4], (bh, l, n), jnp.float32) * 0.5
    y1, s1 = ssd_scan_pallas(x, dt, dA, B, C, chunk=16, interpret=True)
    y2, s2 = ssd_scan_pallas(x, dt, dA, B, C, chunk=64, interpret=True)
    np.testing.assert_allclose(y1, y2, atol=2e-4)
    np.testing.assert_allclose(s1, s2, atol=2e-4)
