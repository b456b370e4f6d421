"""The four-chip campaign cell, ``jscc4.campaign-fcfs-x4``, on four host
devices at a tiny size: ``correct`` comes out false for every fault of
``test_faults.py``, for a grid whose seed axis is broken (lanes of one
seed run another seed's fault draws), and for the bfloat16 control; the
sound program is within every limit.

The paper rule is deterministic, so only the straggler lanes tell the
seeds apart: a broken seed axis shows in their steady sums
(``totals_gap.straggler``), since two seeds' straggler draws end their
campaigns at different times."""

import numpy as np
import pytest

from bench import harness
from bench.tests.test_faults import _break_campaign
from bench.tests.tiny import run_tiny, tiny_cell

CELL = "jscc4.campaign-fcfs-x4"


def _break_grid(monkeypatch, fault: str):
    """``_break_campaign``'s ``fault`` on the sharded grid's run."""
    from repro.core import engine
    real = engine._sharded_run
    monkeypatch.setattr(engine, "_batched_run",
                        lambda *a, **kw: real(*a, **kw))
    _break_campaign(monkeypatch, fault)
    monkeypatch.setattr(engine, "_sharded_run", engine._batched_run)


def _break_seeds(monkeypatch, fault: str, R: int):
    """Run the sharded grid with its flat seed axis (``R`` seeds fastest)
    mis-indexed: ``repeat``, lanes of seeds 2 and 3 draw as seeds 0 and 1
    (half the seeds never drawn); ``collapsed``, every lane draws as the
    first seed."""
    from repro.core import engine
    real = engine._sharded_run

    def broken(arrs, policy, seeds, faults, **kw):
        lane = np.arange(len(seeds))
        r = lane % R
        src = np.where(r >= 2, lane - 2, lane) if fault == "repeat" \
            else lane - r
        return real(arrs, policy, seeds[src], faults, **kw)

    monkeypatch.setattr(engine, "_sharded_run", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "one_lane_altered"])
def test_grid_fault_is_not_correct(fault, monkeypatch, capsys):
    _break_grid(monkeypatch, fault)
    assert run_tiny(CELL, capsys)["correct"] is False


@pytest.mark.parametrize("fault", ["repeat", "collapsed"])
def test_seed_fault_is_not_correct(fault, monkeypatch, capsys):
    R = int(tiny_cell(CELL)["traffic_data"]["seeds_per_point"])
    _break_seeds(monkeypatch, fault, R)
    line = run_tiny(CELL, capsys)
    assert line["correct"] is False
    steady = line["checks"]["totals_gap.straggler"]
    assert steady["value"] > steady["limit"], line["checks"]


def test_sound_grid_is_correct_and_control_is_not():
    cell = tiny_cell(CELL)
    run = harness.kind_module(cell).Cell(cell, 2**36 + 11)
    run.setup()
    run.measure(0.5, harness.Tracer(False, harness.ROOT))
    run.free()
    checks = run.check()
    assert all(c["value"] <= c["limit"] for c in checks), checks
    limits = {c["name"]: c["limit"] for c in checks}
    control = run.control()
    assert any(control[k] > limits[k] for k in limits), (control, limits)
