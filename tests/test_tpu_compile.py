"""Compile-only checks against a described TPU v5e, no chip attached.

Interpret-mode tests cannot see what the chip's compiler refuses: the
kth-free kernel's 1-D output block compiled alone but was refused under
``vmap`` (the campaign grid, the session pool) because the block's last
two dimensions left the (8, 128) tiling.  These tests compile the
kernels and the campaign's scan step for a ``v5e:2x2`` topology at the
JSCC facility's shapes, where they are used.  Nothing runs, so nothing
here says anything about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, SingleDeviceSharding

from repro.core import JSCC_SYSTEMS, Scheduler, make_npb_workload, make_policy
from repro.core.engine import (_batched_run, _chunk_advance, _chunk_init,
                               _sharded_run, _stream_xs)
from repro.data.scenarios import synthetic_swf_arrays, workload_from_arrays
from repro.kernels.kth_free import kth_free_pallas, kth_free_pallas_batched
from repro.service import SessionPool
from repro.sharding.grid import grid_spec, replicated

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


@pytest.mark.parametrize("shape", [(8, S, MAXN), (8, S, 10240)])
def test_kth_free_under_vmap_scan(one_chip, shape):
    hlo = _compiled_text(_vmap_scan(_pallas),
                         _sds(shape, jnp.float32, one_chip),
                         _sds(shape[:2], jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


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


def _campaign_inputs(J=512):
    """Flat-batch inputs of an 8-lane (4 K x 2 seeds) paper campaign on
    the JSCC systems, as ``Scheduler.run`` builds them."""
    w = workload_from_arrays(*synthetic_swf_arrays(J, seed=11),
                             JSCC_SYSTEMS)
    pol = make_policy("paper").with_params(
        k=np.asarray([0.0, 0.1, 0.2, 0.3], np.float32))
    g = Scheduler(pol, warm_start=True, seeds=[0, 1],
                  placer="pallas")._grid(w, False)
    assert g["common"]["core"] == "arrival"
    return g["args"], g["common"]


def test_arrival_core_campaign_step(one_chip):
    """The jitted vmapped arrival-core scan (the paper policy's campaign
    path) with the compiled Pallas placer, at the JSCC shapes."""
    args, common = _campaign_inputs()
    sds = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), args)
    hlo = _batched_run.lower(*sds, **common).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_arrival_core_in_grid_shard_map(topo):
    """The same campaign partitioned over the four chips' ``("grid",)``
    mesh: the kernel compiles inside the shard_map body."""
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("grid",),
                             axis_types=(AxisType.Auto,))
    args, common = _campaign_inputs()
    arrs, pol, seeds, faults = args

    def put(spec):
        return lambda x: _sds(x.shape, x.dtype, NamedSharding(mesh, spec))
    sds = (jax.tree.map(put(replicated), arrs),
           *(jax.tree.map(put(grid_spec), a) for a in (pol, seeds, faults)))
    hlo = _sharded_run.lower(*sds, mesh=mesh, **common).compile().as_text()
    assert "tpu_custom_call" in hlo


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
