"""The rigid-job traffic (``bench/traffic/rigid.py``,
``bench/traffic/campaign-rigid.py``) and the cell that runs it,
``cori2.campaign-rigid``, on the CPU at a tiny size (``bench/conftest.py``):
the stream is seeded and has the shape it claims, and every fault of
``test_faults.py`` makes the cell incorrect.  The cell's end-to-end run
and its bfloat16 control are the cases of ``test_traffic.py`` and
``test_faults.py`` that every cell gets."""

import json

import numpy as np
import pytest

from bench import harness
from bench.tests.test_faults import _break_campaign
from bench.tests.tiny import run_tiny
from bench.traffic import rigid, streams

CELL = "cori2.campaign-rigid"


def _mix():
    return json.loads((harness.BENCH / "traffic" / "campaign-rigid.json")
                      .read_text())


def _tables():
    return json.loads((harness.BENCH / "configs" / "cori2.json")
                      .read_text())["tables"]


def test_stream_is_seeded_and_deterministic():
    big = 2**33 + 7
    a = rigid.rigid_stream(streams.rng_for(big), 3000, _mix(), _tables())
    b = rigid.rigid_stream(streams.rng_for(big), 3000, _mix(), _tables())
    c = rigid.rigid_stream(streams.rng_for(big + 1), 3000, _mix(), _tables())
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[4], c[4])
    arrival = a[1]
    assert (arrival[1:] >= arrival[:-1]).all()


def test_widths_runtimes_and_columns_have_the_shape():
    mix, tables = _mix(), _tables()
    prog, _, t_scale, n_job, cores, runtime = rigid.rigid_stream(
        streams.rng_for(11), 200_000, mix, tables)
    assert cores.min() >= 1 and cores.max() <= 76_416
    assert 0.23 < np.mean(cores == 1) < 0.25             # serial
    parallel = cores[cores > 1]
    pow2 = np.mean((parallel & (parallel - 1)) == 0)
    # 0.75 drawn as 2**round(u), and about 2 % more where round(2**u)
    # lands on a power of two
    assert 0.75 <= pow2 < 0.80, pow2
    assert runtime.max() <= 172_800.0
    # wide jobs run longer (the model's width coupling)
    assert np.median(runtime[cores > 4096]) > 10 * np.median(
        runtime[cores <= 4])
    # a job's columns: its runtime on the reference system, its width
    T = np.asarray(tables["T_true"])
    np.testing.assert_allclose(T[prog, tables["reference_system"]] * t_scale,
                               runtime, rtol=1e-6)
    cpn = np.asarray(tables["cores_per_node"])
    np.testing.assert_array_equal(n_job, np.ceil(cores[:, None] / cpn))
    assert (n_job <= np.asarray(tables["n_nodes"])).all()
    assert set(np.unique(prog)) == set(range(16))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "one_lane_altered"])
def test_fault_is_not_correct(fault, monkeypatch, capsys):
    _break_campaign(monkeypatch, fault)
    assert run_tiny(CELL, capsys)["correct"] is False
