"""Parse post-optimization HLO text for roofline accounting.

``compiled.as_text()`` (after SPMD partitioning) contains the materialized
collective ops.  We sum *operand* bytes of every collective, which is the
amount of data each participating device contributes per invocation — the
quantity that crosses links under a bandwidth-optimal algorithm (up to the
standard 2(n-1)/n ring factor, which we fold into the reported term).
"""

from __future__ import annotations

import re
from collections import defaultdict

# f32[128,256]{1,0} / bf16[4096]{0} / u32[] / pred[8,1]{...}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# matches e.g.:  %ag = bf16[16,512]{1,0} all-gather(bf16[1,512]{1,0} %x), ...
_OP_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+"
    r"([a-z0-9\-]+)(?:-start|-done)?\("
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_hlo_op_bytes(hlo_text: str, op_names=COLLECTIVE_OPS):
    """Sum output bytes of the listed HLO ops.

    Returns {op_name: {"bytes": int, "count": int}}.

    Collective output shape ~= the per-device data volume involved:
      all-gather: output = full gathered buffer (input * group)  — per-device
        traffic under ring is (g-1)/g of this;
      all-reduce: output = reduced buffer; ring traffic ~2x this;
      reduce-scatter: output = scattered shard; traffic ~(g-1) shards;
      all-to-all / collective-permute: output ~= bytes sent per device.
    We record raw output bytes and let the roofline layer apply the
    algorithm factor per op kind.
    """
    out = defaultdict(lambda: {"bytes": 0, "count": 0})
    for line in hlo_text.splitlines():
        m = _OP_LINE_RE.match(line)
        if not m:
            continue
        shape_str, opcode = m.group(1), m.group(2)
        # normalize async forms: all-gather-start / all-reduce-done etc.
        base = None
        for name in op_names:
            if opcode == name or opcode.startswith(name):
                base = name
                break
        if base is None:
            continue
        if opcode.endswith("-done"):
            continue  # avoid double counting start/done pairs
        out[base]["bytes"] += _shape_bytes(shape_str)
        out[base]["count"] += 1
    return dict(out)


# Per-op multiplier converting *output bytes* into approximate bytes that
# cross each device's links (bandwidth-optimal ring algorithms; group factor
# (g-1)/g ~ 1 for the 16-256 way groups we use).
_LINK_FACTOR = {
    "all-gather": 1.0,        # each device receives (g-1)/g of output
    "all-reduce": 2.0,        # reduce-scatter + all-gather
    "reduce-scatter": 1.0,    # output is the shard; each device sends (g-1) shards ~ input
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def parse_collective_bytes(hlo_text: str) -> dict:
    """Return {"per_op": {...}, "link_bytes": float, "total_output_bytes": int}."""
    per_op = parse_hlo_op_bytes(hlo_text)
    link_bytes = 0.0
    total = 0
    for name, rec in per_op.items():
        link_bytes += rec["bytes"] * _LINK_FACTOR[name]
        total += rec["bytes"]
    return {"per_op": per_op, "link_bytes": link_bytes, "total_output_bytes": total}


# --------------------------------------------------------------- stages

# a computation's header: ``%name (params) -> shape {`` (ENTRY marked)
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_REF_RE = re.compile(r"%([\w.\-]+)")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_WHILE_RE = re.compile(r"\b(?:condition|body)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(
    r"\b(?:true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")
_CALL_RE = re.compile(r"\scall\(.*\bto_apply=%?([\w.\-]+)")
_IN_LOOP_RE = re.compile(r"(?:^|/)while(?:/|$)")


def _computations(hlo_text: str):
    """``{computation: [instruction line, ...]}`` and the entry's name."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
        elif cur is not None and _INSTR_RE.match(line):
            comps[cur].append(line)
    return comps, entry


def _callees(line: str):
    """(computation, reached through a loop) pairs that one instruction
    runs as a sequence of top-level instructions."""
    out = [(c, True) for c in _WHILE_RE.findall(line)]
    for one, many in _BRANCH_RE.findall(line):
        names = [one] if one else [n.strip().lstrip("%")
                                   for n in many.split(",")]
        out += [(n, False) for n in names if n]
    m = _CALL_RE.search(line)
    if m:
        out.append((m.group(1), False))
    return out


def op_stages(hlo_text: str, prefix: str = "step.") -> dict[str, str]:
    """Each top-level instruction of a compiled module's text, mapped to
    the stage it was traced under.

    Top-level instructions are those the device runs one by one, and a
    profiler trace names: the entry computation's, and those of the
    computations it reaches through ``while`` (body, condition),
    ``conditional`` branches and ``call`` (not the insides of fusions or
    reducers).  An instruction's stage is the innermost ``<prefix><stage>``
    component of its ``op_name`` (a fusion's is its root's).  Failing
    that, a source path gives ``loop`` where it lies under ``while`` (the
    scan's own slicing, trip count and condition) and ``outside`` where
    the instruction runs in no loop.  A path outside ``while`` on an
    instruction that runs in a loop was not traced where it runs (under
    ``shard_map``, a cached lowering's ``.../shard_map/reduce_window_sum``,
    or ``.../shard_map/reduce-window.7``, which the partitioner names
    after the instruction) and counts as none.  An instruction without a
    source path (a layout copy, a rewritten reduction) takes the stage of
    its users where they all have the same one, repeated back along
    chains of such instructions; what is left is ``loop`` inside a loop's
    computations (carry copies, the loop's tuples, work hoisted into the
    loop) and ``outside`` elsewhere."""
    comps, entry = _computations(hlo_text)
    in_loop, todo = {}, ([(entry, False)] if entry in comps else [])
    while todo:
        comp, looped = todo.pop()
        if comp in in_loop and (in_loop[comp] or not looped):
            continue
        in_loop[comp] = looped
        for line in comps[comp]:
            todo += [(c, looped or lp) for c, lp in _callees(line)
                     if c in comps]
    pat = re.compile(re.escape(prefix) + r"([A-Za-z_]\w*)")
    stages = {}
    for comp, looped in in_loop.items():
        names, refs, label = [], {}, {}
        for line in comps[comp]:
            name = _INSTR_RE.match(line).group(1)
            names.append(name)
            refs[name] = _REF_RE.findall(line.split("=", 1)[1])
            m = _OP_NAME_RE.search(line)
            op_name = m.group(1) if m else ""
            found = pat.findall(op_name)
            if found:
                label[name] = found[-1]
            elif "/" in op_name:
                if _IN_LOOP_RE.search(op_name):
                    label[name] = "loop"
                elif not looped:
                    label[name] = "outside"
        users = {n: [] for n in names}
        for n in names:
            for r in set(refs[n]):
                if r in users:
                    users[r].append(n)
        changed = True
        while changed:
            changed = False
            for n in names:
                seen = {label.get(u) for u in users[n]}
                if n in label or len(seen) != 1:
                    continue
                (st,) = seen
                if st not in (None, "loop", "outside"):
                    label[n] = st
                    changed = True
        for n in names:
            stages[n] = label.get(n, "loop" if looped else "outside")
    return stages
