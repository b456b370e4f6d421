"""Cells cut to a size the CPU runs in seconds, and a run of one that
steers past the device check."""

from __future__ import annotations

import json

from bench import harness

#: per traffic kind, the parameters a test run overrides
TINY = {"campaign": {"jobs": 1200}}

CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite"}


def tiny_cell(name: str) -> dict:
    cell = harness.load_cell(name)
    cell["traffic_data"].update(TINY[cell["traffic_data"]["kind"]])
    return cell


def run_tiny(name: str, capsys, trace: bool = False, seconds: float = 1.0,
             seed: int = 2**40 + 17) -> dict:
    """Run the cell through ``run_cell`` on the CPU; the parsed last line."""
    from bench import run
    cell = tiny_cell(name)
    rc = run.run_cell(cell, {**CPU_DEVICE, "count": cell["chips"]}, seed,
                      seconds, trace)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
