"""Hierarchical compressed-DP trainer (subprocess: needs a (pod, data)
multi-device mesh)."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow       # subprocess, 150-step training run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.optim import AdamWConfig
from repro.train.dp import make_dp_train_step, init_dp_state
from jax.sharding import AxisType

mesh = jax.make_mesh((2, 4), ("pod", "data"),
                     axis_types=(AxisType.Auto,) * 2)
target = jnp.linspace(-1.0, 1.0, 32)

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)

key = jax.random.key(0)
params = {"w": jnp.zeros((16, 32))}
w_true = jax.random.normal(key, (16, 32)) * 0.5
ocfg = AdamWConfig(lr_peak=3e-2, warmup_steps=5, total_steps=150,
                   weight_decay=0.0)

losses = {}
for compress in (False, True):
    p = {"w": jnp.zeros((16, 32))}
    opt, err = init_dp_state(p)
    step = make_dp_train_step(loss_fn, mesh, ocfg, compress_cross_pod=compress)
    for i in range(150):
        k = jax.random.fold_in(key, i)
        x = jax.random.normal(k, (64, 16))
        y = x @ w_true + 0.01 * jax.random.normal(k, (64, 32))
        p, opt, err, loss, gn = step(p, opt, err, {"x": x, "y": y})
    losses[compress] = float(loss)
    print(f"compress={compress}: final loss {float(loss):.5f}")

assert losses[True] < 0.01, losses
assert abs(losses[True] - losses[False]) < 0.01, losses
print("DP COMPRESSED OK")
"""


def test_hierarchical_compressed_dp():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "DP COMPRESSED OK" in out.stdout
