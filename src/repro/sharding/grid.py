"""Campaign-grid sharding primitives.

The training side already spreads work over devices (``train/dp.py``);
this module gives the scheduler's campaign engine the PartitionSpecs
for a 1-D ``("grid",)`` mesh that partitions the flat (fault x policy x
seed) batch axis.  Importing this module never touches jax device state (the
dry-run contract shared with ``launch/mesh.py``).

Per-grid-point simulations are embarrassingly parallel — the scan core
never communicates across the batch axis — so sharding the vmapped
batch is a pure partition: each device runs the identical per-lane op
sequence on its slice and results are bit-identical to the
single-device vmap (asserted in tests/test_sharded_campaign.py).
"""

from __future__ import annotations

from jax.sharding import PartitionSpec

#: the campaign mesh's one axis name (see launch.mesh.make_grid_mesh)
GRID_AXIS = "grid"

#: spec for leaves sharded along the flat batch axis (leading dim)
grid_spec = PartitionSpec(GRID_AXIS)

#: spec for leaves replicated to every device (workload arrays, xs chunks)
replicated = PartitionSpec()
