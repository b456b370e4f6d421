"""The ``step_ns_per_job_lane.<stage>`` readers on a reduced trace and a
stage table made by hand: the seven add up to
``scan_device_ns_per_job_lane``, and each reads nothing where the split
would be wrong or cannot be made."""

import sys

import pytest

from bench import harness, stage_join
from bench.trace_reduce import Reduced

STAGES = ("earliest", "select", "fault", "alloc", "learn", "account",
          "loop")
READERS = [f"step_ns_per_job_lane.{s}" for s in STAGES]

#: instruction -> (stage, seconds in the window); 1,000 scan steps
OPS = {"kth_free_time.7": ("earliest", 0.78), "copy.50": ("earliest", 0.01),
       "fusion.131": ("select", 0.03), "fusion.140": ("fault", 0.02),
       "reduce-window.13": ("alloc", 0.034), "fusion.149": ("alloc", 0.01),
       "fusion.150": ("learn", 0.02), "fusion.154": ("account", 0.01),
       "dynamic_slice.36": ("loop", 0.007), "add.2389": ("loop", 0.002)}
TABLE = {name: st for name, (st, _) in OPS.items()}


def _reduced(extra=None):
    ops = {("%" + n + (" (custom-call)" if n.startswith("kth") else "")):
           [1000, secs] for n, (_, secs) in OPS.items()}
    ops.update(extra or {})
    busy = sum(secs for _, secs in ops.values())
    return Reduced(window_s=1.0, busy_s=busy, devices=1, ops=ops,
                   busy_total_s=busy)


def _run(reduced):
    return {"trace": reduced, "counters": {"lanes_per_device": 12},
            "peaks": {}, "compile_s": 0.0}


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(stage_join, "_table", [TABLE])


def test_the_seven_add_up_to_the_step(table):
    run = _run(_reduced())
    got = {s: harness.reader(r)(run) for s, r in zip(STAGES, READERS)}
    whole = harness.reader("scan_device_ns_per_job_lane")(run)
    assert sum(got.values()) == pytest.approx(whole, rel=1e-12)
    # kth-free events x lanes: 1,000 steps of 12 lanes
    assert got["earliest"] == pytest.approx(0.79e9 / 12000)
    assert got["loop"] == pytest.approx(0.009e9 / 12000)


def test_unknown_operations_past_one_percent_read_nothing(table):
    busy = sum(secs for _, secs in OPS.values())
    small = _run(_reduced({"%fusion.999": [10, 0.009 * busy]}))
    assert harness.reader(READERS[0])(small) is not None
    big = _run(_reduced({"%fusion.999": [10, 0.011 * busy]}))
    assert all(harness.reader(r)(big) is None for r in READERS)


def test_nothing_without_a_trace_or_a_kernel(table):
    assert all(harness.reader(r)(_run(None)) is None for r in READERS)
    no_kernel = _reduced()
    del no_kernel.ops["%kth_free_time.7 (custom-call)"]
    assert all(harness.reader(r)(_run(no_kernel)) is None for r in READERS)


def test_nothing_where_the_program_has_no_stage_table(monkeypatch):
    # a program without repro.obs (one that predates the stage scopes)
    import repro
    monkeypatch.setattr(stage_join, "_table", [])
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert all(harness.reader(r)(_run(_reduced())) is None for r in READERS)


def test_nothing_before_any_recorded_run(monkeypatch):
    from repro import obs
    monkeypatch.setattr(stage_join, "_table", [])
    monkeypatch.setattr(obs, "_programs", ())
    assert harness.reader(READERS[0])(_run(_reduced())) is None


# a compiled module's text, cut down: a loop body holding a custom call,
# its operand's layout copy, a fusion under a nested scope, the
# condition, and an epilogue outside the loop
TEXT = """\
HloModule jit_f

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %add.9 = f32[4]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(f)/while/body/step.alloc/add"}
}

%body.5 (arg.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%arg.1), index=0
  %gte.1 = f32[4]{0} get-tuple-element(%arg.1), index=1
  %copy.5 = f32[4]{0} copy(%gte.1)
  %kth_free_time.7 = f32[4]{0} custom-call(%copy.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/closed_call/step.earliest/jit(kth_free_time)/pallas_call"}
  %fusion.4 = f32[4]{0} fusion(%kth_free_time.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/closed_call/step.select/step.alloc/add"}
  %add.7 = s32[] add(%gte.0, %gte.0), metadata={op_name="jit(f)/while/body/add"}
  ROOT %tuple.3 = (s32[], f32[4]{0}) tuple(%add.7, %fusion.4)
}

%cond.6 (arg.2: (s32[], f32[4])) -> pred[] {
  %arg.2 = (s32[], f32[4]{0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%arg.2), index=0
  ROOT %lt.1 = pred[] compare(%gte.2, %gte.2), direction=LT, metadata={op_name="jit(f)/while/cond/lt"}
}

ENTRY %main.8 (p.1: (s32[], f32[4])) -> f32[4] {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  %while.1 = (s32[], f32[4]{0}) while(%p.1), condition=%cond.6, body=%body.5, metadata={op_name="jit(f)/while"}
  %gte.3 = f32[4]{0} get-tuple-element(%while.1), index=1
  ROOT %multiply.2 = f32[4]{0} multiply(%gte.3, %gte.3), metadata={op_name="jit(f)/mul"}
}
"""


def test_op_stages_parses_a_compiled_text():
    from repro.utils.hlo import op_stages
    st = op_stages(TEXT)
    assert st["kth_free_time.7"] == "earliest"      # the custom call
    assert st["copy.5"] == "earliest"               # its operand's copy
    assert st["fusion.4"] == "alloc"                # the innermost scope
    assert st["lt.1"] == "loop" and st["add.7"] == "loop"
    assert st["while.1"] == "loop"
    assert st["multiply.2"] == "outside"
    assert "add.9" not in st                        # inside a fusion
