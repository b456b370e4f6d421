"""The reduction from a profiler trace to busy time, idle share, kernel
time and roofline share, on a trace recorded on one TPU v5e chip: three
calls of the vmapped kth-free Pallas kernel over [12, 4, 136] node
tables, each with a copy before and a reduce after, 2 ms apart."""

import pathlib

import pytest

from bench import harness, trace_reduce

TRACE = pathlib.Path(__file__).parent / "data" / "kth_free_v5e.xplane.pb"
KERNEL = "%vmap_jit_kth_free_time_batched__.2 (custom-call)"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_busy_union_and_idle_share(reduced):
    assert reduced.devices == 1
    assert reduced.busy_s == pytest.approx(227.663e-6, abs=1e-12)
    assert reduced.window_s == pytest.approx(7.020436e-3, abs=1e-12)
    assert reduced.idle_share == pytest.approx(0.9675713873, abs=1e-9)


def test_window_span_cuts_operations_to_it():
    ops = [(0, 10, "%a"), (20, 40, "%k (custom-call)"), (50, 60, "%a"),
           (90, 120, "%b")]
    spans = [(15, 100, "bench.window"), (40, 50, "campaign.run")]
    r = trace_reduce.reduce_events([ops], spans, "bench.window")
    assert r.window_s == pytest.approx(85e-9)
    assert r.busy_s == pytest.approx(40e-9)       # 20 + 10 + 10 inside
    assert r.kernel("%k") == (1, pytest.approx(20e-9))
    assert r.ops["%b"] == [1, pytest.approx(10e-9)]
    assert "%a" in r.ops and r.ops["%a"][0] == 1  # the one at 0-10 is out
    assert r.breakdown()["idle_gaps"][0] == ["no span",
                                             pytest.approx(30e-9)]
    assert ["campaign.run", pytest.approx(10e-9)] in \
        r.breakdown()["idle_gaps"]


def test_window_span_must_be_in_the_trace():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events([[(0, 10, "%a")]], [], "bench.window")
    with pytest.raises(ValueError):
        trace_reduce.reduce(TRACE, "bench.window")


def test_kernel_time_and_breakdown(reduced):
    n, secs = reduced.kernel("kth_free")
    assert n == 3 and secs == pytest.approx(224.902e-6, abs=1e-12)
    top = reduced.breakdown()["device_ops"]
    assert top[0] == [KERNEL, pytest.approx(224.902e-6, abs=1e-12)]
    assert {name for name, _ in top} == {KERNEL, "%copy", "%reduce"}
    gaps = reduced.breakdown()["idle_gaps"]
    assert gaps[0][0] == "no span" and gaps[0][1] > 3e-3


def test_roofline_and_share_of_busy(reduced):
    ctx = {"trace": reduced, "peaks": harness.peaks("TPU v5 lite"),
           "counters": {"lanes_per_device": 12, "systems": 4,
                        "max_nodes": 136}}
    per_event = 12 * (4 * 136 * 4 + 4 * 4 + 4 * 4)
    want = 100 * 3 * per_event / 819e9 / 224.902e-6
    assert harness.reader("kth_free_roofline")(ctx) == pytest.approx(want)
    assert harness.reader("kth_free_share_of_busy")(ctx) == pytest.approx(
        100 * 224.902 / 227.663)
    assert harness.reader("device_idle_share.campaign")(ctx) == \
        pytest.approx(96.75713873, abs=1e-6)
    # three kernel events, one scan step each, over 12 lanes
    assert harness.reader("scan_device_ns_per_job_lane")(ctx) == \
        pytest.approx(227.663e3 / (3 * 12))


def test_readers_return_nothing_without_a_trace():
    ctx = {"trace": None, "counters": {}, "peaks": {}}
    for name in ("kth_free_roofline", "kth_free_share_of_busy",
                 "scan_device_ns_per_job_lane",
                 "device_idle_share.campaign"):
        assert harness.reader(name)(ctx) is None


def test_containers_are_left_out_and_unions_merge():
    evs = [(0, 100, "%while"), (0, 10, "%a"), (20, 30, "%b"),
           (25, 28, "%c"), (40, 100, "%d")]
    assert [e[2] for e in trace_reduce.leaves(evs)] == ["%a", "%c", "%d"]
    assert trace_reduce.merge([(0, 10), (5, 12), (20, 30), (30, 31)]) == \
        [[0, 12], [20, 31]]
    assert trace_reduce.short_name(
        '%k.1 = f32[4] custom-call(f32[4] %x), custom_call_target="t"') \
        == "%k.1 (custom-call)"
