"""Compile-only checks against a described TPU v5e, no chip attached.

Interpret-mode tests cannot see what the chip's compiler refuses: the
kth-free kernel's 1-D output block compiled alone but was refused under
``vmap`` (the campaign grid, the session pool) because the block's last
two dimensions left the (8, 128) tiling.  These tests compile the
kernels and the campaign's scan step for a ``v5e:2x2`` topology at the
JSCC facility's shapes, where they are used.  Nothing runs, so nothing
here says anything about results or times.

Under ``vmap`` the kth-free kernel folds every lane into one invocation
(``repro.kernels.kth_free``); ``repro.obs.kth_free_calls()`` says how many
lanes and grid steps each lowering of the folded kernel took.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, SingleDeviceSharding

from repro import obs
from repro.core import JSCC_SYSTEMS, Scheduler, make_npb_workload, make_policy
from repro.core.engine import (_batched_run, _chunk_advance, _chunk_init,
                               _sharded_run, _stream_xs)
from repro.data.scenarios import synthetic_swf_arrays, workload_from_arrays
from repro.kernels.kth_free import kth_free_pallas, kth_free_pallas_batched
from repro.kernels.kth_free.kernel import LANE_BLOCK_BYTES
from repro.service import SessionPool
from repro.sharding.grid import grid_spec, replicated
from repro.utils.hlo import op_stages

S, MAXN = 4, 136            # JSCC: 4 systems, the largest has 136 nodes


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _vmap_scan(kernel):
    """The campaign grid's shape of call: ``vmap`` over lanes of a
    ``lax.scan`` whose step calls the kernel and feeds its result back."""
    def grid(free, n_req):
        def lane(f, n):
            def body(c, _):
                kth = kernel(c, n)
                return c + kth[..., None], kth
            return jax.lax.scan(body, f, None, length=3)
        return jax.vmap(lane)(free, n_req)
    return grid


def _pallas(free, n_req):
    return kth_free_pallas(free, n_req, interpret=False)


def _pallas_batched(free, n_req):
    return kth_free_pallas_batched(free, n_req, interpret=False)


@pytest.mark.parametrize("shape", [(S, MAXN), (S, 10240)])
def test_kth_free_alone(one_chip, shape):
    hlo = _compiled_text(_pallas, _sds(shape, jnp.float32, one_chip),
                         _sds(shape[:1], jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


def _folds(fn, *args, **kw):
    """(compiled text, ``obs.kth_free_calls()`` of that one lowering)."""
    jax.clear_caches()
    seen = len(obs.kth_free_calls())
    hlo = fn.lower(*args, **kw).compile().as_text()
    return hlo, obs.kth_free_calls()[seen:]


@pytest.mark.parametrize("shape", [(8, S, MAXN), (8, S, 10240),
                                   (12, 2, 9688)])
def test_kth_free_under_vmap_scan(one_chip, shape):
    """The lanes fold into one invocation: one block where they fit
    ``LANE_BLOCK_BYTES``, else a grid over groups of as many as fit
    (six lanes of [4, 10240]).  NERSC Cori's 12 lanes of [2, 9688] take
    one block of 930,048 bytes, within the walk's scoped VMEM."""
    L, s, n = shape
    hlo, folds = _folds(jax.jit(_vmap_scan(_pallas)),
                        _sds(shape, jnp.float32, one_chip),
                        _sds(shape[:2], jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo
    per_lane = s * n * 4
    G = min(L, LANE_BLOCK_BYTES // per_lane)
    assert folds == (obs.KthFreeCall(lanes=L, grid_steps=-(-L // G),
                                     block_bytes=G * per_lane),)
    assert (folds[0].grid_steps > 1) == (n == 10240)


@pytest.mark.parametrize("W", [17, 33])
def test_kth_free_batched(one_chip, W):
    hlo = _compiled_text(_pallas_batched,
                         _sds((W, S, MAXN), jnp.float32, one_chip),
                         _sds((W, S), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


def test_kth_free_batched_under_vmap_scan(one_chip):
    hlo = _compiled_text(_vmap_scan(_pallas_batched),
                         _sds((8, 17, S, MAXN), jnp.float32, one_chip),
                         _sds((8, 17, S), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


def _campaign_inputs(J=512, per_job=True):
    """Flat-batch inputs of a 12-lane (6 K x 2 seeds) paper campaign on
    the JSCC systems, as ``Scheduler.run`` builds them (``per_job``
    False: each job runs as its class)."""
    w = workload_from_arrays(*synthetic_swf_arrays(J, seed=11),
                             JSCC_SYSTEMS)
    if not per_job:
        w = dataclasses.replace(w, t_scale=None, n_req_job=None)
    pol = make_policy("paper").with_params(
        k=np.asarray([0.0, 0.05, 0.1, 0.2, 0.5, 0.85], np.float32))
    g = Scheduler(pol, warm_start=True, seeds=[0, 1],
                  placer="pallas")._grid(w, False)
    assert g["common"]["core"] == "arrival"
    return g["args"], g["common"]


def test_arrival_core_campaign_step(one_chip):
    """The jitted vmapped arrival-core scan (the paper policy's campaign
    path) with the compiled Pallas placer, at the JSCC shapes: the scan's
    step runs exactly one kth-free custom call, named after
    ``kth_free_time`` and under ``step.earliest``, which walks all 12
    lanes in one block."""
    args, common = _campaign_inputs()
    sds = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), args)
    hlo, folds = _folds(_batched_run, *sds, **common)
    calls = [line.split(" = ", 1)[0].strip().lstrip("%")
             for line in hlo.splitlines() if "custom-call(" in line]
    kth = [c for c in calls if "kth_free" in c]
    assert len(kth) == 1 and kth[0].startswith("kth_free_time"), calls
    assert op_stages(hlo)[kth[0]] == "earliest"
    assert folds == (obs.KthFreeCall(lanes=12, grid_steps=1,
                                     block_bytes=12 * S * MAXN * 4),)


def _grid_shard_map_text(topo, args, common):
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("grid",),
                             axis_types=(AxisType.Auto,))
    arrs, pol, seeds, faults = args

    def put(spec):
        return lambda x: _sds(x.shape, x.dtype, NamedSharding(mesh, spec))
    sds = (jax.tree.map(put(replicated), arrs),
           *(jax.tree.map(put(grid_spec), a) for a in (pol, seeds, faults)))
    return _sharded_run.lower(*sds, mesh=mesh, **common).compile().as_text()


def test_arrival_core_in_grid_shard_map(topo):
    """The same campaign partitioned over the four chips' ``("grid",)``
    mesh: the kernel compiles inside the shard_map body."""
    hlo = _grid_shard_map_text(topo, *_campaign_inputs())
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("per_job", [False, True])
def test_grid_shard_map_keeps_the_step_stages(topo, one_chip, per_job):
    """Under ``shard_map`` the cumsum of ``_alloc_mask`` lowers to
    instructions whose paths lost the step's scopes (the partitioner
    names them after themselves, ``.../shard_map/reduce-window.7``): the
    stage table still gives them ``alloc``, as on one chip, so that a
    sharded campaign's stages read as a one-chip campaign's."""
    args, common = _campaign_inputs(per_job=per_job)
    sds = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), args)
    texts = {"one chip": _batched_run.lower(*sds, **common).compile()
             .as_text(),
             "grid": _grid_shard_map_text(topo, args, common)}
    for where, hlo in texts.items():
        stages = op_stages(hlo)
        windows = {n: st for n, st in stages.items()
                   if n.startswith("reduce-window")}
        assert windows and set(windows.values()) == {"alloc"}, (where,
                                                                 windows)
    one, grid = (set(op_stages(t).values()) for t in texts.values())
    assert one == grid


def test_chunk_advance_step(one_chip):
    """One chunk advance of the chunked campaign (the million-job path)."""
    args, common = _campaign_inputs()
    xs, _ = _stream_xs(args[0], args[1], common["core"], common["retries"])
    carries = jax.eval_shape(
        lambda *a: _chunk_init(*a, mesh=None, **common), *args)
    sds = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                       (args, carries, jax.tree.map(lambda x: x[:256], xs)))
    hlo = _chunk_advance.lower(*sds[0], sds[1], sds[2], mesh=None,
                               nsteps=256, **common).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_session_pool_event_step(one_chip):
    """The service's vmapped event step over an 8-session pool."""
    w = make_npb_workload(JSCC_SYSTEMS)
    scheds = [Scheduler(make_policy("paper", k=k), warm_start=True,
                        placer="pallas") for k in np.linspace(0, 0.35, 8)]
    pool = SessionPool(scheds, w, capacity=64)
    try:
        state = (pool._pol, pool._ctx, pool._carry,
                 jnp.zeros(pool.n, jnp.float32))
        sds = jax.tree.map(
            lambda x: _sds(np.shape(x), jnp.result_type(x), one_chip), state)
        hlo = pool._step.lower(*sds).compile().as_text()
    finally:
        pool.close()
    assert "tpu_custom_call" in hlo


def test_cori_width_campaign_step(one_chip):
    """The arrival-core campaign at NERSC Cori's width, `[S, maxN] =
    [2, 9688]`, with per-job runtimes and widths (a trace replay's own
    jobs): the folded kernel still walks all 12 lanes in one block of
    930 KB, and the per-job reads compile under ``step.job``."""
    from repro.core import CORI_SYSTEMS
    rng = np.random.default_rng(5)
    J = 512
    cores = rng.integers(1, 76_416, J).astype(np.float64)
    w = workload_from_arrays(np.sort(rng.uniform(0, 1e5, J)),
                             np.exp(rng.uniform(1.0, 11.0, J)), cores,
                             CORI_SYSTEMS)
    pol = make_policy("paper").with_params(
        k=np.asarray([0.0, 0.05, 0.1, 0.2, 0.5, 0.85], np.float32))
    g = Scheduler(pol, warm_start=True, seeds=[0, 1],
                  placer="pallas")._grid(w, False)
    sds = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                       g["args"])
    hlo, folds = _folds(_batched_run, *sds, **g["common"])
    assert folds == (obs.KthFreeCall(lanes=12, grid_steps=1,
                                     block_bytes=12 * 2 * 9688 * 4),)
    assert "job" in set(op_stages(hlo).values())
