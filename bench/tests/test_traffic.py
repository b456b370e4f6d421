"""Every cell of BENCHMARK.json runs end to end on the CPU at a tiny
size, and the command refuses to run without a TPU."""

import json

import pytest

from bench import harness
from bench.tests.tiny import run_tiny

CELLS = [w["name"] for w in harness.spec_file()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end(name, capsys):
    line = run_tiny(name, capsys)
    cell = harness.load_cell(name)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert line["device"]["count"] == cell["chips"]


def test_traced_run_reports_per_layer_metrics(capsys):
    line = run_tiny(CELLS[0], capsys, trace=True)
    assert line["correct"] is True
    # the CPU trace holds no TPU plane: only the program's counters read
    assert set(line["metrics"]) == {"compile_s"}


def test_no_tpu_exits_nonzero_with_no_result(capsys):
    from bench import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert not [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("{")]


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.load_cell("no.such-cell")


def test_seed_fixes_the_inputs():
    from bench.traffic import streams
    tr = json.loads((harness.BENCH / "traffic" / "campaign-fcfs.json")
                    .read_text())
    cat = ["BT", "EP", "IS", "LU", "SP"]
    big = 2**33 + 5
    a = streams.npb_stream(streams.rng_for(big), 500, tr, cat)
    b = streams.npb_stream(streams.rng_for(big), 500, tr, cat)
    c = streams.npb_stream(streams.rng_for(big + 1), 500, tr, cat)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[1] == c[1]).all()
    assert (a[1][1:] >= a[1][:-1]).all()


def test_sharded_grid_runs_on_four_devices(capsys):
    """The campaign kind's ``shards``/``seeds_per_point`` parameters (a
    four-chip cell's, kept for the sharded-grid cell listed in PERF.md)
    on four host devices."""
    from bench import run
    from bench.tests.tiny import CPU_DEVICE, tiny_cell
    cell = tiny_cell(CELLS[0])
    cell["traffic_data"].update(shards=4, seeds_per_point=4)
    assert run.run_cell(cell, {**CPU_DEVICE, "count": 4}, 2**34 + 9, 1.0,
                        False) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] % 48 == 0
