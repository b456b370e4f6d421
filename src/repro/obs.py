"""Where the scan step's device time goes: the step's stage names, the
programs the last ``Scheduler.run`` dispatched, and the table from their
compiled instructions to stages.

Every core's step runs its stages under ``jax.named_scope("step.<stage>")``
(``stage``), so the compiled HLO carries the stage in each instruction's
``metadata={op_name=...}``; a fusion carries the path of its root.
A profiler trace names device operations by instruction, so a trace is
read by stage through ``stage_table()``:

    res = sched.run(w)                 # records the dispatched programs
    ...                                # jax.profiler trace of the run
    table = obs.stage_table()          # {"fusion.149": "alloc", ...}

``Scheduler.run`` only keeps the record (jitted function, static keyword
arguments, arguments as ``jax.ShapeDtypeStruct``); ``stage_table`` lowers
and compiles those programs again, which the persistent compile cache
answers where it is on, and memoises the result per record.

Stages (``STAGES``):

- ``earliest``: kth-free times, the arrival floor and the outage push
  (and the EASY head's recheck on trial allocations);
- ``select``: the selection key, the policy's choice of system, and in
  the queued cores the choice of slot (eligibility, power feasibility);
- ``fault``: the fault draw and the realised runtime and energy it scales;
- ``alloc``: the node-free (and node-power) table update of a placement;
- ``learn``: the learned ``C_tab`` / ``T_tab`` / ``runs`` updates;
- ``account``: the Kahan totals, busy time and per-step outputs;
- ``push``: admission into the pending buffer, its pop and re-queue;
- ``advance``: the next event and the clock (event and conservative
  cores);
- ``job``: the reads of a workload's per-job columns (``t_scale``,
  ``n_req_job``) and the products that turn a class's runtime, energy
  and draw into the job's own; no operation where the workload has no
  such columns.

An instruction outside every stage but inside the scan's loop is
``"loop"`` (xs slicing, the trip counter, carry copies, the condition);
anything else is ``"outside"`` (set-up and the result epilogue).

How the kth-free kernel is invoked: each lowering of its lane-folded form
(``repro.kernels.kth_free``, a vmapped ``kth_free_pallas``) notes the
lanes one invocation covers, its grid steps and the node-free bytes of
one block, readable as ``kth_free_calls()``.  An unvmapped call notes
nothing.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import numpy as np

from repro.utils.hlo import op_stages

STAGES = ("earliest", "select", "fault", "alloc", "learn", "account",
          "push", "advance", "job")
PREFIX = "step."

_programs: tuple = ()      # ((fn, static kwargs, shapes), ...) of the last run
_tables: dict = {}         # record key -> stage table
_kth_free: list = []       # KthFreeCall per lowering of the folded kernel


class KthFreeCall(NamedTuple):
    lanes: int             # lanes one invocation covers
    grid_steps: int        # groups of lanes it walks one after another
    block_bytes: int       # node-free bytes of one block


def stage(name: str):
    """The ``jax.named_scope`` of one step stage."""
    if name not in STAGES:
        raise ValueError(f"unknown step stage {name!r}; known: {STAGES}")
    return jax.named_scope(PREFIX + name)


def _shape(x):
    sharding = getattr(x, "sharding", None) \
        if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding,
                                weak_type=getattr(x, "weak_type", False))


def record(programs) -> None:
    """Keep ``programs``, the ``(jitted fn, static kwargs, args)`` one
    ``Scheduler.run`` dispatched, with every argument reduced to its
    ``ShapeDtypeStruct`` (no array is held)."""
    global _programs
    _programs = tuple((fn, dict(kw), jax.tree.map(_shape, args))
                      for fn, kw, args in programs)


def programs() -> tuple:
    """The record of the last ``Scheduler.run``."""
    return _programs


def _key(progs) -> tuple:
    out = []
    for fn, kw, args in progs:
        leaves, tree = jax.tree.flatten(args)
        out.append((fn, tuple(sorted(kw.items())), tuple(leaves), tree))
    return tuple(out)


def stage_table() -> dict[str, str]:
    """``{instruction name -> stage}`` over the compiled programs of the
    last ``Scheduler.run`` (``STAGES``, ``"loop"`` or ``"outside"``).  A name
    that two of the programs give to instructions of different stages is
    left out, so a trace event under it stays unknown.  Raises
    ``LookupError`` before any run."""
    progs = _programs
    if not progs:
        raise LookupError("no Scheduler.run has been recorded")
    key = _key(progs)
    if key not in _tables:
        table, clash = {}, set()
        for fn, kw, args in progs:
            text = fn.lower(*args, **kw).compile().as_text()
            for name, st in op_stages(text, PREFIX).items():
                if table.setdefault(name, st) != st:
                    clash.add(name)
        _tables[key] = {n: s for n, s in table.items() if n not in clash}
    return _tables[key]


def note_kth_free(lanes: int, grid_steps: int, block_bytes: int) -> None:
    """Note one lowering of the lane-folded kth-free kernel."""
    _kth_free.append(KthFreeCall(lanes, grid_steps, block_bytes))


def kth_free_calls() -> tuple[KthFreeCall, ...]:
    """The lane-folded kth-free kernel's lowerings since the process
    started, oldest first.  A program that JAX has lowered before and
    serves from its caches notes nothing again."""
    return tuple(_kth_free)
