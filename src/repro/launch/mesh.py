"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required by the dry-run contract: only dryrun.py
sets the 512-device XLA flag before jax initializes).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) over 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) over 512 chips — the 'pod' axis
    carries only data parallelism (hierarchical gradient reduction over DCN).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_grid_mesh(shards="auto"):
    """1-D ``("grid",)`` mesh over the local devices for campaign-grid
    sharding (``Scheduler(shards=...)``): the flat (fault x policy x seed)
    batch axis of a campaign spreads across its devices via shard_map
    (repro.sharding.grid).  ``shards``: "auto"/None = every local device,
    or an explicit count <= the local device count."""
    n_local = len(jax.devices())
    n = n_local if shards in (None, "auto") else int(shards)
    if not 1 <= n <= n_local:
        raise ValueError(f"shards={shards!r} not in 1..{n_local} "
                         f"(local devices)")
    return jax.make_mesh((n,), ("grid",), axis_types=(AxisType.Auto,))


def make_elastic_mesh(n_devices: int, model_parallel: int = 16):
    """Rebuild a (data, model) mesh from however many devices survive —
    the elastic-restart path (data dim shrinks, model dim is preserved so
    checkpoints reshard without repartitioning logic)."""
    assert n_devices % model_parallel == 0
    return jax.make_mesh((n_devices // model_parallel, model_parallel),
                         ("data", "model"), axis_types=(AxisType.Auto,) * 2)
