"""Per-job runtimes and widths (``Workload.t_scale``, ``Workload.n_req_job``).

A trace replay's job keeps its own runtime (``t_scale`` times its class's)
and its own node count on each system; the scheduler still predicts and
learns per class.  Pinned here, on seeded small facilities and streams of
several widths with ties in arrivals and in node free times:

- every core (arrival FCFS, EASY, the event core under FCFS, EASY and a
  power cap, conservative, DVFS tiers) against the float64 mirror
  ``simulate_py``;
- the chunked and sharded campaign paths and the service (``Dispatcher``,
  ``SessionPool``) against the monolithic batch run, bit for bit;
- the arrival core's campaign grid against the benchmark's plain
  reference ``bench/reference/fcfs_rigid.py``;
- ``workload_from_arrays`` giving each job its trace runtime on the
  reference system and its own width;
- a workload without per-job columns lowering to the program it lowered
  to before the columns existed, and one with them lowering to a pinned
  program, each with its stage table.
"""

import hashlib
import pathlib
import sys
from dataclasses import replace

import numpy as np
import pytest

from repro import obs
from repro.core import (JSCC_SYSTEMS, FaultConfig, Scheduler, SimConfig,
                        make_npb_workload, make_policy, simulate_py)
from repro.core.engine import Workload
from repro.data.scenarios import workload_from_arrays
from repro.service import Dispatcher, SessionPool, own_columns, whatif

ROOT = pathlib.Path(__file__).resolve().parent.parent
FAULTS = (FaultConfig(), FaultConfig(straggler_prob=0.2, straggler_factor=2.0))

#: node counts of the seeded facilities: tiny rows, uneven rows, the JSCC
#: facility's
FACILITIES = {"tiny": (3, 5), "uneven": (8, 2, 13),
              "jscc": (38, 136, 58, 51)}


def rigid_workload(n_nodes, J=36, P=4, seed=0, gap=6.0):
    """A contended stream over ``n_nodes``: class tables drawn from the
    seed, integer arrivals with repeats (ties), dyadic runtime scales (so
    float32 times stay exact and free times tie) and per-job widths
    anywhere in [1, n_nodes[s]]."""
    rng = np.random.default_rng(seed)
    n_nodes = np.asarray(n_nodes, np.int32)
    S = len(n_nodes)
    n_req = rng.integers(1, n_nodes + 1, (P, S)).astype(np.int32)
    T = rng.uniform(40.0, 300.0, (P, S))
    E = T * n_req * rng.uniform(150.0, 400.0, (1, S))
    C = E / rng.uniform(1e3, 1e4, (P, 1))
    arrival = (np.cumsum(rng.integers(0, 3, J)) * gap).astype(np.float32)
    return Workload(
        prog=rng.integers(0, P, J).astype(np.int32), arrival=arrival,
        k_job=np.full(J, np.nan, np.float32), n_req=n_req,
        T_true=T, C_true=C, E_true=E, T_pred=T, C_pred=C, n_nodes=n_nodes,
        programs=tuple(f"c{p}" for p in range(P)),
        systems=tuple(f"s{s}" for s in range(S)),
        idle_w=rng.uniform(50.0, 150.0, S).astype(np.float32),
        t_scale=rng.choice([0.25, 0.5, 1.0, 1.0, 2.0, 4.0],
                           J).astype(np.float32),
        n_req_job=rng.integers(1, n_nodes + 1, (J, S)).astype(np.int32))


#: core -> (SimConfig fields, Scheduler extras)
CORES = {
    "arrival": (dict(), dict()),
    "easy": (dict(mode="easy_backfill", queue_window=4), dict()),
    "events": (dict(core="events"), dict()),
    "events_easy": (dict(core="events", queue="easy_backfill",
                         queue_window=4), dict()),
    "events_capped": (dict(core="events", power_cap=0.0), dict()),
    "conservative": (dict(queue="conservative", queue_window=4), dict()),
    "dvfs": (dict(mode="dvfs_paper"), dict()),
}


def _cap(w):
    """A cap that binds: the idle floor plus a third of the widest
    class draw."""
    idle = float(np.sum(w.idle_w * w.n_nodes))
    return idle + float(np.max(w.E_true / w.T_true)) / 3.0


def assert_matches_mirror(w, fields, extra):
    if fields.get("power_cap") == 0.0:
        fields = {**fields, "power_cap": _cap(w)}
    cfg = SimConfig(**{"mode": "paper", "k": 0.1, "warm_start": True,
                       **fields})
    rj = Scheduler(cfg.policy(), placer="jnp", warm_start=True,
                   engine=cfg.core or None, **extra).run(w)
    rp = simulate_py(w, cfg, check_reservations=cfg.queue == "conservative")
    np.testing.assert_array_equal(np.asarray(rj.system), rp["system"])
    for f in ("start", "finish", "runtime"):
        np.testing.assert_allclose(np.asarray(getattr(rj, f)), rp[f],
                                   rtol=1e-5, atol=1e-3, err_msg=f)
    np.testing.assert_allclose(np.asarray(rj.energy), rp["energy"],
                               rtol=1e-5)
    for f in ("total_energy", "makespan", "total_wait", "idle_energy"):
        np.testing.assert_allclose(float(getattr(rj, f)), rp[f], rtol=1e-5,
                                   err_msg=f)
    np.testing.assert_array_equal(np.asarray(rj.backfilled),
                                  rp["backfilled"])
    # each job held its own width
    sel = np.asarray(rj.system)
    np.testing.assert_array_equal(
        np.asarray(rj.nodes), w.n_req[w.prog, sel] if w.n_req_job is None
        else w.n_req_job[np.arange(len(sel)), sel])
    return rj


@pytest.mark.parametrize("facility", list(FACILITIES))
@pytest.mark.parametrize("core", list(CORES))
def test_every_core_matches_the_mirror(core, facility):
    w = rigid_workload(FACILITIES[facility], seed=len(core) + len(facility))
    rj = assert_matches_mirror(w, *CORES[core])
    # the jobs ran at their own runtimes: never all at their class's
    cls_T = w.T_true[w.prog, np.asarray(rj.system)]
    assert not np.allclose(np.asarray(rj.runtime)
                           / np.asarray(rj.runtime).min(), cls_T / cls_T.min())


@pytest.mark.parametrize("column", ["t_scale", "n_req_job"])
@pytest.mark.parametrize("core", list(CORES))
def test_one_column_alone_matches_the_mirror(core, column):
    """Per-job widths without per-job runtimes, and runtimes without
    widths: each core runs the column it has."""
    w = replace(rigid_workload(FACILITIES["uneven"], seed=len(core)),
                **{column: None})
    assert_matches_mirror(w, *CORES[core])


def test_mirror_without_columns_is_unchanged():
    """Per-job columns of ones and class widths realise the class run."""
    w = rigid_workload(FACILITIES["uneven"], seed=4)
    plain = replace(w, t_scale=None, n_req_job=None)
    same = replace(w, t_scale=np.ones(len(w.prog), np.float32),
                   n_req_job=w.n_req[w.prog])
    cfg = SimConfig(mode="paper", k=0.1, warm_start=True)
    a, b = simulate_py(plain, cfg), simulate_py(same, cfg)
    for f in ("system", "start", "energy", "total_energy", "idle_energy"):
        np.testing.assert_array_equal(a[f], b[f])
    ja = Scheduler("paper", warm_start=True).run(plain)
    jb = Scheduler("paper", warm_start=True).run(same)
    for f in ("system", "start", "energy", "busy"):
        np.testing.assert_array_equal(np.asarray(getattr(ja, f)),
                                      np.asarray(getattr(jb, f)))
    # the per-job program rounds the running sum's product on its own
    # (engine._running_mean); the class program may fuse it
    np.testing.assert_allclose(np.asarray(ja.T_tab), np.asarray(jb.T_tab),
                               rtol=1e-6)


@pytest.mark.parametrize("spec", [dict(chunk=7), dict(shards=1),
                                  dict(chunk=5, shards=1)])
@pytest.mark.parametrize("totals_only", [False, True])
def test_chunked_and_sharded_paths_equal_the_scan(spec, totals_only):
    w = rigid_workload(FACILITIES["jscc"], seed=9)
    pol = make_policy("paper").with_params(
        k=np.asarray([0.0, 0.2], np.float32))
    kw = dict(warm_start=True, faults=FAULTS, seeds=[0, 1])
    ref = Scheduler(pol, **kw).run(w, totals_only=totals_only).to_dict()
    got = Scheduler(pol, **kw, **spec).run(
        w, totals_only=totals_only).to_dict()
    for f, v in ref.items():
        if isinstance(v, (np.ndarray, np.generic)) or hasattr(v, "shape"):
            assert np.asarray(got[f]).tobytes() == np.asarray(v).tobytes(), f


@pytest.mark.parametrize("queue", ["fcfs", "easy_backfill:window=4",
                                   "conservative:window=4"])
def test_dispatcher_replays_the_batch_run(queue):
    w = rigid_workload(FACILITIES["uneven"], J=24, seed=5)
    pol = make_policy("paper", k=0.1)
    batch = Scheduler(pol, warm_start=True, queue=queue,
                      engine="events").run(w)
    d = Dispatcher(w, pol, warm_start=True, queue=queue)
    for j in range(len(w.prog)):
        d.drive(until=float(w.arrival[j]))
        d.submit(int(w.prog[j]), float(w.arrival[j]), **own_columns(w, j))
    d.drain()
    live = d.result()
    for f in ("system", "start", "finish", "energy", "runtime", "nodes",
              "total_energy", "makespan", "busy", "T_tab", "idle_energy"):
        assert np.asarray(getattr(live, f)).tobytes() == \
            np.asarray(getattr(batch, f)).tobytes(), f


def test_pool_sessions_equal_lone_sessions():
    w = rigid_workload(FACILITIES["tiny"], J=20, seed=6)
    scheds = [Scheduler(make_policy("paper", k=k), warm_start=True, seeds=i)
              for i, k in enumerate((0.05, 0.3))]
    pool = SessionPool(scheds, w)
    for j in range(len(w.prog)):
        t = float(w.arrival[j])
        for i in range(pool.n):
            pool.drive(t, session=i)
            pool.submit(i, int(w.prog[j]), t, **own_columns(w, j))
    pool.drain()
    for i, s in enumerate(scheds):
        d = Dispatcher.from_scheduler(s, w)
        for j in range(len(w.prog)):
            d.drive(until=float(w.arrival[j]))
            d.submit(int(w.prog[j]), float(w.arrival[j]), **own_columns(w, j))
        d.drain()
        a, b = pool.result(i), d.result()
        for f in ("system", "start", "energy", "nodes", "total_energy"):
            assert np.asarray(getattr(a, f)).tobytes() == \
                np.asarray(getattr(b, f)).tobytes(), f
    pool.close()


def test_submissions_are_checked_against_the_session():
    w = rigid_workload(FACILITIES["tiny"], J=6, seed=2)
    d = Dispatcher(w, "paper", warm_start=True)
    with pytest.raises(ValueError):
        d.submit(0, 0.0, nodes=[4, 1])            # wider than system 0
    with pytest.raises(ValueError):
        d.submit(0, 0.0, t_scale=0.0)
    j = d.submit(0, 0.0, t_scale=3.0, nodes=[2, 5])
    assert float(d._arrs["t_scale"][j]) == 3.0
    assert whatif(d, 1, 1.0, t_scale=0.5, nodes=[1, 1])["job"]["prog"] == 1
    plain = Dispatcher(replace(w, t_scale=None, n_req_job=None), "paper")
    with pytest.raises(ValueError):
        plain.submit(0, 0.0, t_scale=2.0)
    with pytest.raises(ValueError):
        Scheduler("paper").run(replace(w, n_req_job=w.n_req_job + 5))
    with pytest.raises(ValueError):
        Scheduler("paper").run(replace(w, t_scale=-w.t_scale))


def test_arrival_core_matches_the_benchmark_reference():
    """The campaign grid (K x fault draws) against the plain float64
    reference that decides the benchmark's ``correct``."""
    sys.path.insert(0, str(ROOT))
    from bench.reference.common import straggler_factors
    from bench.reference.fcfs_rigid import fcfs_rigid
    w = rigid_workload(FACILITIES["jscc"], J=60, seed=13, gap=2.0)
    ks = [0.0, 0.1, 0.5]
    pol = make_policy("paper").with_params(k=np.asarray(ks, np.float32))
    res = Scheduler(pol, warm_start=True, faults=FAULTS, seeds=7).run(
        w, totals_only=True)
    tables = {"T_true": w.T_true, "C_true": w.C_true, "E_true": w.E_true,
              "n_req": w.n_req, "n_nodes": w.n_nodes}
    factor = np.stack([straggler_factors(7, len(w.prog), f.straggler_prob,
                                         f.straggler_factor)
                       for f in FAULTS for _ in ks])
    ref = fcfs_rigid(tables, w.prog, w.arrival, w.t_scale, w.n_req_job,
                     ks * len(FAULTS), factor)
    got = {k: np.asarray(getattr(res, k)).reshape((6,) + ref[k].shape[1:])
           for k in ref}
    np.testing.assert_array_equal(got["runs"], ref["runs"])
    for f in ("total_energy", "makespan", "total_wait", "slowdown_sum",
              "max_wait", "busy", "C_tab", "T_tab"):
        np.testing.assert_allclose(got[f], ref[f], rtol=2e-5, err_msg=f)


@pytest.mark.parametrize("calibrate", [False, True])
def test_trace_replay_keeps_each_jobs_runtime_and_width(calibrate):
    rng = np.random.default_rng(3)
    J = 400
    submit = np.sort(rng.uniform(0, 1e5, J))
    runtime = np.exp(rng.uniform(1.0, 11.0, J))
    procs = rng.integers(1, 9000, J).astype(np.float64)
    w = workload_from_arrays(submit, runtime, procs, JSCC_SYSTEMS,
                             calibrate=calibrate)
    theta = np.asarray([s.peak_flops_node * s.efficiency * s.cores_per_node
                        for s in JSCC_SYSTEMS])
    ref = int(np.argmax(theta))
    np.testing.assert_allclose(w.T_true[w.prog, ref] * w.t_scale, runtime,
                               rtol=1e-6)
    cores = np.asarray([s.cores_per_node for s in JSCC_SYSTEMS])
    nodes = np.asarray([s.n_nodes for s in JSCC_SYSTEMS])
    np.testing.assert_array_equal(
        w.n_req_job, np.clip(np.ceil(procs[:, None] / cores), 1, nodes))
    # the class tables are still per class
    assert w.n_req.shape == (len(w.programs), len(JSCC_SYSTEMS))


#: sha256 prefixes of each program's StableHLO text (no source
#: locations) and of its stage table (CPU, ``placer="jnp"`` unless named).
#: The class-only entries are as the engine lowered them before per-job
#: columns existed, with today's kth-free walk: a workload without per-job
#: columns must lower to exactly these.  The ``rigid`` entries pin a
#: workload with both per-job columns (the arrival core's campaign, as a
#: trace replay runs it, and the event core) as the engine lowered it
#: before its placement stages became shared helpers.
PROGRAMS = {
    "arrival": ("b7ecccb64ee9e38b", "936ebd643483a13b"),
    "arrival_pallas": ("069ef7ea7a266118", "e74609028bfaca64"),
    "easy": ("c1e112851129743a", "83a7ba0304366fb9"),
    "events": ("59bed6a1e1e8c8ea", "31a598e871275057"),
    "cons": ("832ddfbff5b0487b", "7a29f567105c816f"),
    "chunk": ("a83dfd6375c5d3f5", "935045f5d3dfcee0"),
    "full": ("de9af62e8648a378", "6e9a503b0ec8ddb0"),
    "rigid": ("d7e36bad6ff8fc15", "bdd1fe87221b038c"),
    "rigid_events": ("d237b0fa9a2f53bf", "60e7e1e5b6bc328d"),
}
SPECS = {"arrival": {}, "arrival_pallas": dict(placer="pallas_interpret"),
         "easy": dict(queue="easy_backfill:window=4"),
         "events": dict(engine="events"),
         "cons": dict(queue="conservative:window=4"),
         "chunk": dict(chunk=8), "full": {},
         "rigid": {}, "rigid_events": dict(engine="events")}
WITH_COLUMNS = ("rigid", "rigid_events")


def _digest(x) -> str:
    return hashlib.sha256(x.encode()).hexdigest()[:16]


def _program_digests(w, name, totals):
    """(StableHLO digest, stage-table digest, stages) of ``name``'s
    campaign program over ``w``."""
    pol = make_policy("paper").with_params(
        k=np.asarray([0.0, 0.2], np.float32))
    s = Scheduler(pol, faults=FAULTS[:1] + (FaultConfig(0.05, 2.0),),
                  warm_start=True, **{"placer": "jnp", **SPECS[name]})
    text = s.lower(w, totals_only=totals).as_text()
    s.run(w, totals_only=totals)
    table = obs.stage_table()
    return (_digest(text), _digest(repr(sorted(table.items()))),
            set(table.values()))


@pytest.mark.parametrize("name", [n for n in PROGRAMS
                                  if n not in WITH_COLUMNS])
def test_workload_without_columns_lowers_to_the_same_program(name):
    w = make_npb_workload(JSCC_SYSTEMS, repeats=4,
                          arrivals=np.arange(20, dtype=np.float32) * 7.0)
    text, table, stages = _program_digests(w, name, name != "full")
    assert "job" not in stages
    assert (text, table) == PROGRAMS[name]


@pytest.mark.parametrize("name", WITH_COLUMNS)
def test_workload_with_columns_lowers_to_the_same_program(name):
    w = rigid_workload(FACILITIES["jscc"], J=20, seed=9)
    text, table, stages = _program_digests(w, name, True)
    assert "job" in stages
    assert (text, table) == PROGRAMS[name]


@pytest.mark.parametrize("spec", [dict(), dict(engine="events"),
                                  dict(queue="conservative:window=4")])
def test_per_job_reads_run_under_the_job_stage(spec):
    w = rigid_workload(FACILITIES["uneven"], J=16, seed=1)
    Scheduler("paper", warm_start=True, placer="jnp", **spec).run(
        w, totals_only=True)
    assert "job" in set(obs.stage_table().values())
