"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a cell can have, and when the reference computed in
bfloat16 (the control) stands in the program's place."""

import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.tiny import run_tiny, tiny_cell

CAMPAIGN = "jscc4.campaign-fcfs"


def _break_campaign(monkeypatch, fault: str):
    """Wrap the program's batched campaign run with ``fault``."""
    from repro.core import engine
    real = engine._batched_run

    def broken(arrs, policy, seeds, faults, **kw):
        out = dict(real(arrs, policy, seeds, faults, **kw))
        B = out["total_energy"].shape[0]
        if fault == "state_unchanged":
            # every step hands its carry back as it got it
            out = {k: jnp.zeros_like(v) for k, v in out.items()}
            out["runs"] = jnp.ones_like(out["runs"])
            out["C_tab"] = jnp.broadcast_to(arrs["C_true"], out["C_tab"].shape)
            out["T_tab"] = jnp.broadcast_to(arrs["T_true"], out["T_tab"].shape)
        elif fault == "half_batch":
            # the second half of the lanes is never computed: it repeats
            # the first
            h = B // 2
            out = {k: v.at[h:].set(v[:h][: B - h]) for k, v in out.items()}
        elif fault == "answer_altered":
            # one job per lane placed on another system than its own
            runs = out["runs"]
            flat = runs.reshape(B, -1)
            i = jnp.argmax(flat, axis=1)
            flat = flat.at[jnp.arange(B), i].add(-1)
            flat = flat.at[jnp.arange(B), (i + 1) % flat.shape[1]].add(1)
            out["runs"] = flat.reshape(runs.shape)
        elif fault == "one_lane_altered":
            # the first lane alone reports one job on another system
            runs = out["runs"]
            out["runs"] = runs.at[0, 0, 0].add(-1).at[0, 0, 1].add(1)
        return out

    monkeypatch.setattr(engine, "_batched_run", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "one_lane_altered"])
def test_campaign_fault_is_not_correct(fault, monkeypatch, capsys):
    _break_campaign(monkeypatch, fault)
    assert run_tiny(CAMPAIGN, capsys)["correct"] is False


@pytest.mark.parametrize("name", [w["name"] for w in
                                  harness.spec_file()["workloads"]
                                  if w["chips"] == 1])
def test_control_is_not_correct(name):
    """The program is within every limit; the bfloat16 reference in its
    place is outside one of them."""
    cell = tiny_cell(name)
    run = harness.kind_module(cell).Cell(cell, 2**36 + 11)
    run.setup()
    run.measure(0.5, harness.Tracer(False, harness.ROOT))
    run.free()
    limits = {c["name"]: c["limit"] for c in run.check()}
    assert all(c["value"] <= c["limit"] for c in run.check())
    control = run.control()
    assert any(control[k] > limits[k] for k in limits), (control, limits)
