"""The scan step's stage scopes, as the compiled program carries them.

Each core's step runs its stages under ``jax.named_scope("step.<stage>")``;
``repro.obs.stage_table()`` maps the compiled instructions of the last
``Scheduler.run`` to those stages.  Here, on a tiny workload of the JSCC
facility's shape: every top-level instruction inside the scan's loop
lands in one of the core's stages or in ``loop``, each stage owns some
instruction, the kth-free placement lies in ``earliest``, the run keeps
shapes and not arrays, and the table is built only when asked for.  The
workload is a trace replay, so its jobs carry per-job columns and every
core also runs the ``job`` stage.
"""

import re

import numpy as np
import pytest
import jax

from repro import obs
from repro.core import JSCC_SYSTEMS, FaultConfig, Scheduler, make_policy
from repro.data.scenarios import synthetic_swf_arrays, workload_from_arrays
from repro.utils.hlo import _callees, _computations, op_stages

SIX = ("earliest", "select", "fault", "alloc", "learn", "account")
#: the per-job columns' reads and products (a trace replay's own jobs)
JOB = ("job",)
FAULTS = (FaultConfig(),
          FaultConfig(straggler_prob=0.05, straggler_factor=2.0))
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def w():
    return workload_from_arrays(*synthetic_swf_arrays(40, seed=11),
                                JSCC_SYSTEMS)


def _sched(**kw):
    pol = make_policy("paper").with_params(
        k=np.asarray([0.0, 0.2], np.float32))
    return Scheduler(pol, faults=FAULTS, **kw)


def _compiled_texts():
    return [fn.lower(*args, **kw).compile().as_text()
            for fn, kw, args in obs.programs()]


#: the op_name of the vmapped scan's own ``while``, directly under the jit
SCAN = re.compile(r'op_name="jit\(\w+\)/vmap\(\)/while"')


def _loop_lines(text):
    """Top-level instruction lines of the scan's loop: the computations
    the scan's ``while`` runs (body and condition), and what they run in
    turn (none in a program without the scan: a chunked run's init and
    finish)."""
    comps, entry = _computations(text)
    todo, seen = [entry], set()
    while todo:                  # the computations outside any loop
        comp = todo.pop()
        seen.add(comp)
        todo += [c for line in comps[comp] for c, looped in _callees(line)
                 if not looped and c not in seen]
    todo = [c for comp in seen for line in comps[comp] if SCAN.search(line)
            for c, _ in _callees(line)]
    seen, out = set(), []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        out += comps[comp]
        todo += [c for line in comps[comp] for c, _ in _callees(line)
                 if c in comps]
    return out


def _check_core(stages, kth_needle="kth_free"):
    """Every loop instruction of every recorded program is in ``stages``
    or ``loop``; each of ``stages`` owns one; kth-free work is earliest;
    the table agrees with each program, and holds all of a lone one."""
    table = obs.stage_table()
    texts = _compiled_texts()
    owned, n_kth, n_loop = set(), 0, 0
    for text in texts:
        per = op_stages(text)
        assert all(per[n] == st for n, st in table.items() if n in per)
        if len(texts) == 1:
            assert per == table
        for line in _loop_lines(text):
            name = INSTR.match(line).group(1)
            st = per[name]
            assert st in stages + ("loop",), (name, st)
            owned.add(st)
            n_loop += 1
            m = OP_NAME.search(line)
            if m and kth_needle in m.group(1):
                assert st == "earliest", (name, m.group(1))
                n_kth += 1
    assert n_loop and n_kth
    assert set(stages) <= owned, set(stages) - owned
    return table


@pytest.mark.parametrize("placer", ["pallas_interpret", "jnp"])
def test_arrival_core_stages(w, placer):
    _sched(placer=placer).run(w, totals_only=True)
    _check_core(SIX + JOB)


def test_easy_core_stages(w):
    _sched(placer="jnp", queue="easy_backfill:window=4").run(
        w, totals_only=True)
    _check_core(SIX + ("push",) + JOB)


@pytest.mark.parametrize("spec", [dict(engine="events"),
                                  dict(queue="conservative:window=4")])
def test_event_cores_stages(w, spec):
    _sched(placer="jnp", **spec).run(w, totals_only=True)
    _check_core(SIX + ("push", "advance") + JOB)


def test_chunked_run_records_its_three_programs(w):
    _sched(placer="jnp", chunk=16).run(w, totals_only=True)
    names = [fn.__name__ for fn, _, _ in obs.programs()]
    # 40 jobs in chunks of 16: the full chunk and the remainder
    assert names == ["_chunk_init", "_chunk_advance", "_chunk_advance",
                     "_chunk_finish"]
    assert [kw["nsteps"] for fn, kw, _ in obs.programs()[1:3]] == [16, 8]
    _check_core(SIX + JOB)


def test_run_keeps_shapes_and_the_table_is_memoised(w, monkeypatch):
    s = _sched(placer="jnp")
    s.run(w, totals_only=True)
    leaves = [leaf for _, _, args in obs.programs()
              for leaf in jax.tree.leaves(args)]
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct)
                          for x in leaves)
    table = obs.stage_table()
    assert obs.stage_table() is table
    s.run(w, totals_only=True)                 # same shapes: same table
    assert obs.stage_table() is table
    # building the table is stage_table()'s work alone, never run()'s
    built = dict(obs._tables)

    def refuse(*a, **k):
        raise AssertionError("run() parsed a compiled program")
    monkeypatch.setattr(obs, "op_stages", refuse)
    w2 = workload_from_arrays(*synthetic_swf_arrays(24, seed=3),
                              JSCC_SYSTEMS)
    s.run(w2, totals_only=True)
    assert obs._tables == built


def test_stage_names_are_checked():
    assert obs.STAGES[:6] == SIX
    with pytest.raises(ValueError):
        obs.stage("sort")


def test_no_table_before_any_run(monkeypatch):
    monkeypatch.setattr(obs, "_programs", ())
    with pytest.raises(LookupError):
        obs.stage_table()


# a compiled module's text, cut down: an entry with a loop, the loop's
# body and condition, a fusion and a reducer
TEXT = """\
HloModule jit_f

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %add.9 = f32[4]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(f)/while/body/step.learn/add"}
}

%region_0.2 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %add.3 = f32[] add(%a.1, %b.1), metadata={op_name="jit(f)/reduce_sum"}
}

%body.5 (arg.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%arg.1), index=0
  %gte.1 = f32[4]{0} get-tuple-element(%arg.1), index=1
  %copy.5 = f32[4]{0} copy(%gte.1)
  %kth_free_time.7 = f32[4]{0} custom-call(%copy.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/closed_call/step.earliest/jit(kth_free_time)/pallas_call"}
  %fusion.4 = f32[4]{0} fusion(%kth_free_time.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/closed_call/step.select/jit(g)/step.learn/add"}
  %copy.6 = f32[4]{0} copy(%fusion.4)
  %constant.2 = s32[] constant(1)
  %add.7 = s32[] add(%gte.0, %constant.2), metadata={op_name="jit(f)/while/body/add"}
  ROOT %tuple.3 = (s32[], f32[4]{0}) tuple(%add.7, %copy.6)
}

%cond.6 (arg.2: (s32[], f32[4])) -> pred[] {
  %arg.2 = (s32[], f32[4]{0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%arg.2), index=0
  %constant.3 = s32[] constant(8)
  ROOT %lt.1 = pred[] compare(%gte.2, %constant.3), direction=LT, metadata={op_name="jit(f)/while/cond/lt"}
}

ENTRY %main.8 (p.1: f32[4]) -> f32[] {
  %p.1 = f32[4]{0} parameter(0)
  %constant.4 = s32[] constant(0)
  %tuple.1 = (s32[], f32[4]{0}) tuple(%constant.4, %p.1)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.1), condition=%cond.6, body=%body.5, metadata={op_name="jit(f)/while"}
  %gte.3 = f32[4]{0} get-tuple-element(%while.1), index=1
  %constant.5 = f32[] constant(0)
  ROOT %reduce.2 = f32[] reduce(%gte.3, %constant.5), dimensions={0}, to_apply=%region_0.2, metadata={op_name="jit(f)/reduce_sum"}
}
"""


def test_op_stages_on_a_compiled_text():
    st = op_stages(TEXT)
    # the innermost scope wins, and a fusion carries its own path
    assert st["fusion.4"] == "learn"
    assert st["kth_free_time.7"] == "earliest"
    # a copy with no source path takes its only user's stage
    assert st["copy.5"] == "earliest"
    # the carry copy feeds the loop's tuple: the loop's own
    assert st["copy.6"] == "loop" and st["tuple.3"] == "loop"
    assert st["add.7"] == "loop" and st["lt.1"] == "loop"
    assert st["while.1"] == "loop"
    assert st["reduce.2"] == "outside" and st["p.1"] == "outside"
    # the insides of fusions and reducers are not top-level
    assert "add.9" not in st and "add.3" not in st
    assert op_stages(TEXT, prefix="nothing.")["fusion.4"] == "loop"
