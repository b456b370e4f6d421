"""The batched EASY scan against the float64 mirror.

The EASY step scores every pending slot in one batched pass: every trial
allocation in a step is computed against the SAME starting node-free
table, so the window slots are independent and the first-fit choice is a
masked argmin over slot index.  These tests hold that construction to the
float64 mirror ``simulate_py``, whose ``_easy_order_py`` replays the
reservation and the no-delay guard slot by slot: chosen systems, DVFS
tiers and backfill flags exactly, starts and finishes within ``rtol=1e-5,
atol=1e-3``, runtimes, energies and totals within ``rtol=1e-5`` — for every
registered policy, warm and cold, with and without outage windows, on
synthetic and trace-replay streams, at windows 1 and 2, and under both
result paths (full per-job arrays and ``totals_only``).

The mirror covers the deterministic path only, so two cases compare in
other ways: with straggler and failure draws the default placer is held
bit for bit to ``placer="sort"`` and each job's runtime to its class's
times its own fault factor; a forced placer is held bit for bit to the
default one.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (JSCC_SYSTEMS, FaultConfig, Scheduler, SimConfig,
                        make_policy, policy_names, simulate_py)
from repro.core.engine import _fault_factor, _fault_vec
from repro.data.scenarios import (load_swf, maintenance_windows,
                                  make_stream_workload, workload_from_trace)

PER_JOB = ("system", "start", "finish", "wait", "energy", "runtime",
           "nodes", "backfilled")
TOTALS = ("total_energy", "makespan", "total_wait", "max_wait",
          "slowdown_sum", "busy", "n_backfilled", "C_tab", "T_tab", "runs")
#: totals the mirror reports (``slowdown_sum`` derived from its jobs)
MIRROR_TOTALS = ("total_energy", "makespan", "total_wait", "max_wait",
                 "slowdown_sum", "idle_energy")


def assert_matches_mirror(w, mode, *, k=0.1, window=0, warm=True, seeds=7,
                          totals_only=False):
    """Batched EASY (policy ``mode`` under ``easy_backfill``, the policy's
    own window unless ``window``) against ``simulate_py``."""
    cfg = SimConfig(mode=mode, k=k, warm_start=warm, seed=seeds,
                    queue="easy_backfill", queue_window=window)
    rj = Scheduler(cfg.policy(), warm_start=warm, seeds=seeds).run(
        w, totals_only=totals_only)
    rp = simulate_py(w, cfg)
    rp["slowdown_sum"] = np.sum((rp["wait"] + rp["runtime"])
                                / rp["runtime"])
    assert int(rj.n_backfilled) == rp["n_backfilled"]
    for f in MIRROR_TOTALS:
        np.testing.assert_allclose(float(getattr(rj, f)), rp[f], rtol=1e-5,
                                   err_msg=f)
    if totals_only:
        return rj
    np.testing.assert_array_equal(np.asarray(rj.system), rp["system"])
    np.testing.assert_array_equal(np.asarray(rj.backfilled),
                                  rp["backfilled"])
    np.testing.assert_array_equal(np.asarray(rj.tier), rp["tier"])
    for f in ("start", "finish"):
        np.testing.assert_allclose(np.asarray(getattr(rj, f)), rp[f],
                                   rtol=1e-5, atol=1e-3, err_msg=f)
    for f in ("energy", "runtime"):
        np.testing.assert_allclose(np.asarray(getattr(rj, f)), rp[f],
                                   rtol=1e-5, err_msg=f)
    return rj


def assert_bit_identical(ra, rb, totals_only=False):
    fields = TOTALS if totals_only else PER_JOB + TOTALS
    for field in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ra, field)), np.asarray(getattr(rb, field)),
            err_msg=f"placers disagree on {field!r}")


def _contended_stream(n=40, rate=1.0, kind="poisson", seed=3):
    """High arrival rate => real queueing: held heads and live backfill
    candidates, so the scan faces real decisions."""
    return make_stream_workload(JSCC_SYSTEMS, n, arrival=kind, rate=rate,
                                seed=seed, pred_noise=0.05)


# ----------------------------------------------- whole-registry sweep (slow)

@pytest.mark.slow
@pytest.mark.parametrize("name", policy_names())
@pytest.mark.parametrize("warm", [True, False], ids=["warm", "cold"])
def test_registry_bit_identity(name, warm):
    """Every registered policy (DVFS tiers included), warm and cold, at
    window 4: the batched scan makes the mirror's decisions."""
    assert_matches_mirror(_contended_stream(), name, window=4, warm=warm)


# --------------------------------------------------- targeted quick coverage

@pytest.mark.parametrize("name", ["paper", "random", "queue_aware", "ucb"])
def test_bit_identity_quick(name):
    """Quick-tier subset: selector axes that exercise every batched input
    (tables, availability, PRNG keys, optimism bounds)."""
    assert_matches_mirror(_contended_stream(), name, window=6)


def test_bit_identity_cold_with_faults():
    """Cold tables + straggler/failure draws, which the mirror does not
    model: the placers agree bit for bit, and each job ran for its class's
    runtime on its system times its own fault factor (keyed by job id)."""
    w = _contended_stream(seed=11)
    fault = FaultConfig(straggler_prob=0.3, straggler_factor=2.5,
                        failure_prob=0.2, restart_overhead=0.5)
    pol = make_policy("easy_backfill", k=0.1)
    kw = dict(warm_start=False, seeds=7, faults=fault)
    r = Scheduler(pol, **kw).run(w)
    assert_bit_identical(r, Scheduler(pol, placer="sort", **kw).run(w))
    fault_key = jax.random.split(jax.random.key(7))[1]
    factors = jax.vmap(lambda j: _fault_factor(fault_key, j,
                                               _fault_vec(fault)))(
        jnp.arange(len(w.prog)))
    sel = np.asarray(r.system)
    want = jnp.asarray(w.T_true)[w.prog, sel] * factors
    np.testing.assert_array_equal(np.asarray(r.runtime), np.asarray(want))
    assert (np.asarray(factors) != 1.0).any()
    assert int(r.n_backfilled) > 0


def test_bit_identity_with_outage_windows():
    """Outage pushes hit both the candidate scoring and the head recheck
    (the reduced single-system push must match the full per-system one)."""
    outage = maintenance_windows(
        4, {1: [(0.0, 400.0)], 2: [(100.0, 300.0), (500.0, 650.0)]})
    w = make_stream_workload(JSCC_SYSTEMS, 35, arrival="poisson", rate=0.8,
                             seed=8, outage=outage)
    assert_matches_mirror(w, "easy_backfill")
    assert_matches_mirror(w, "easy_queue_aware")


def test_bit_identity_trace_replay():
    swf = "\n".join(
        f"{i+1} {i*15} 0 {200 + 61*i % 2400} {2 ** (2 + i % 7)} 100.0 0 "
        f"{2 ** (2 + i % 7)} 1000 0 1 1 1 1 1 1 -1 -1"
        for i in range(50)).splitlines()
    w = workload_from_trace(load_swf(swf), JSCC_SYSTEMS)
    assert_matches_mirror(w, "easy_backfill", k=0.2)


def test_bit_identity_window_overflow_and_degenerate():
    """window=1 (every placement is the forced head) and an overflowing
    window=2 on a bursty stream: the FCFS-fallback edge must agree too."""
    w = _contended_stream(kind="bursty", rate=0.8, seed=5)
    for window in (1, 2):
        assert_matches_mirror(w, "paper", window=window)


def test_bit_identity_totals_only():
    """Campaign-memory path: the masked Kahan accumulator's aggregates
    against the mirror's totals."""
    w = _contended_stream(seed=13)
    assert_matches_mirror(w, "easy_backfill", totals_only=True)


@pytest.mark.parametrize("placer", ["sort", "pallas_interpret"])
def test_bit_identity_forced_placers(placer):
    """Explicit placer forcing routes the batched scoring through the
    broadcast batched kernel (not the shared-sort fast path): bit for bit
    the default placer's run."""
    w = _contended_stream(n=25)
    pol = make_policy("easy_backfill", k=0.1)
    kw = dict(warm_start=True, seeds=7)
    assert_bit_identical(Scheduler(pol, **kw).run(w),
                         Scheduler(pol, placer=placer, **kw).run(w))
