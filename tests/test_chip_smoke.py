"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes.

Each phase function runs in-process at a few thousand jobs (the script
itself runs them at 10^4-10^6 on the chip); ``main`` must refuse a
machine without a TPU; the four-chip phase runs on four virtual CPU
devices in a subprocess (the device-count flag must precede jax
initialization).  The persistent compile cache of the entry points is
checked in subprocesses too, so this process keeps JAX's defaults.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


@pytest.fixture(scope="module")
def cols():
    return chip_smoke.stream_columns(3000)


def test_phase_paper():
    out = chip_smoke.phase_paper()
    assert out["lanes"] == 6
    assert out["dE_pct_K20"] < 0            # K=20 % saves energy


def test_phase_campaign(cols):
    out = chip_smoke.phase_campaign(cols, chunk=1024)
    assert out["jobs"] == 3000 and out["lanes"] == 8
    assert not out["pallas_kernel_in_step"]   # CPU: the jnp radix select


def test_phase_backfilling(cols):
    out = chip_smoke.phase_backfilling(cols, J=1000)
    assert max(out["conservative"]["peak_power_W"]) <= 52000.0


def test_phase_agreement(cols):
    out = chip_smoke.phase_agreement(cols, J=1000)
    assert set(chip_smoke.AGREEMENT_QUEUES) <= set(out)


def test_phase_service(tmp_path):
    out = chip_smoke.phase_service(tmp_path, J=40)
    assert out["sessions"] == 9
    assert (tmp_path / "single").is_dir() and (tmp_path / "pool").is_dir()


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "'cpu'" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


def _python(script, *, env=None, timeout=600):
    full = dict(os.environ, PYTHONPATH=f"{ROOT / 'src'}:{ROOT}",
                JAX_PLATFORMS="cpu", **(env or {}))
    out = subprocess.run([sys.executable, "-c", script], env=full,
                         capture_output=True, text=True, timeout=timeout,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_four_chips_phase_on_virtual_devices():
    rep = _python("""
import json
import chip_smoke
print(json.dumps(chip_smoke.phase_four_chips(
    chip_smoke.stream_columns(2000), chunk=512)))
""", env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert rep["bit_identical"] and rep["result_devices"] == 4
    assert rep["lanes"] == 16


_CACHED_RUN = """
import json
import jax
from repro.core import JSCC_SYSTEMS, Scheduler, make_npb_workload
from repro.launch.compile_cache import enable_compile_cache
where = enable_compile_cache()
hits = []
jax.monitoring.register_event_listener(
    lambda e, **_: hits.append(e) if e.endswith("cache_hits") else None)
Scheduler("paper", warm_start=True).run(make_npb_workload(JSCC_SYSTEMS))
print(json.dumps({"dir": where, "hits": len(hits)}))
"""


def test_compile_cache_env_dir_is_read_back(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the entry points' cache goes
    there and a second process reads the first one's entries."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    first = _python(_CACHED_RUN, env=env)
    assert first["dir"] == str(tmp_path) and any(tmp_path.iterdir())
    second = _python(_CACHED_RUN, env=env)
    assert second["hits"] > 0


def test_compile_cache_default_dir_is_fixed_and_ignored():
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
