#!/usr/bin/env python3
"""Smoke run of the scheduler's main path on one TPU chip.

    python3 chip_smoke.py                # one chip: every phase below
    python3 chip_smoke.py --four-chips   # four chips: the sharded campaign
                                         # and its one-device twin, only

One process drives the chip; nothing here starts a child.  Every phase
goes through the public API (``Scheduler.run`` and the service
handlers) and raises on the first wrong result, so the script exits
non-zero and the JSON result line is never printed.  The phases:

  device       jax.devices()[0].platform must be "tpu" (no CPU carry-on)
  paper        the NPB suite on the four JSCC systems over a K sweep (the
               paper's experiment), placements == the float64 mirror
  campaign     a 10^6-job synthetic SWF stream, 8 lanes (4 K x 2 seeds),
               chunked, totals only, default placer; the compiled step
               must hold the Pallas kernel on the chip
  backfilling  the stream's first 10^5 jobs under EASY (window 16) and
               conservative (window 8, 52 kW cap), 2 lanes each
  agreement    a 10^4-job prefix under fcfs / EASY / conservative against
               the float64 mirror ``simulate_py``, and the kth-free
               kernel against the sort reference bit for bit
  service      a diurnal submission stream through ``handle`` (one
               session) and ``handle_pool`` (8 sessions): drive, what-if,
               checkpoint, restore into a fresh session, drain; decisions
               == the event-core batch run

Each phase prints one JSON line with its key totals and its smoke
timings: ``compile_s`` is JAX's own lowering + compile time inside the
phase (jax.monitoring), ``warm_s`` the rest of the phase's wall time,
``cache_hits`` the persistent-cache entries it read.  They are
smoke timings, not benchmark metrics.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.core import (JSCC_SYSTEMS, Scheduler, SimConfig,  # noqa: E402
                        make_npb_workload, make_policy, simulate_py)
from repro.core.engine import BIG  # noqa: E402
from repro.data.scenarios import (make_stream_workload,  # noqa: E402
                                  synthetic_swf_arrays, workload_from_arrays)
from repro.kernels.kth_free import (kth_free_time,  # noqa: E402
                                    kth_free_time_batched)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.scheduler_service import handle, handle_pool  # noqa: E402
from repro.service import Dispatcher, SessionPool  # noqa: E402

#: the campaign stream: J, generator seed, chunk length (README, docs/API)
STREAM_J, STREAM_SEED, CHUNK = 10**6, 11, 65536
#: rtol of f32 engine totals against the float64 mirror at 10^4 jobs
#: (tests/test_differential_scale.py)
MIRROR_RTOL = 1e-4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def _finite(*xs):
    return all(np.all(np.isfinite(np.asarray(x))) for x in xs)


def _k_grid(ks):
    return make_policy("paper").with_params(
        k=np.asarray(ks, np.float32))


# ------------------------------------------------------------------ device

def phase_device(min_count: int = 1) -> dict:
    """The accelerator JAX sees; raises SystemExit unless it is a TPU."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found — jax.devices()[0]"
                         f".platform is {d.platform!r}")
    if len(devs) < min_count:
        raise SystemExit(f"chip_smoke: {min_count} chips needed, JAX sees "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ------------------------------------------------------------------- paper

def phase_paper() -> dict:
    """The paper's experiment (examples/quickstart.py): the NPB suite
    submitted at once to the JSCC systems, warm profile tables, a K
    sweep in one jitted call; every K's placements == the mirror."""
    w = make_npb_workload(JSCC_SYSTEMS)
    ks = np.array([0.0, 0.05, 0.10, 0.20, 0.50, 0.85], np.float32)
    res = Scheduler(_k_grid(ks), warm_start=True).run(w)
    E = np.asarray(res.total_energy)
    M = np.asarray(res.makespan)
    sel = np.asarray(res.system)
    check(E.shape == (len(ks),) and _finite(E, M), "paper: bad totals")
    for i, k in enumerate(ks):
        rp = simulate_py(w, SimConfig(mode="paper", k=float(k),
                                      warm_start=True))
        check(np.array_equal(sel[i], rp["system"]),
              f"paper: K={k} placements {sel[i]} != mirror {rp['system']}")
        check(math.isclose(E[i], rp["total_energy"], rel_tol=1e-5),
              f"paper: K={k} energy {E[i]} != mirror {rp['total_energy']}")
    i20 = int(np.flatnonzero(ks == np.float32(0.2))[0])
    return {"jobs": len(w.prog), "lanes": len(ks),
            "energy_J": E.tolist(), "makespan_s": M.tolist(),
            "dE_pct_K20": float(100 * (E[i20] - E[0]) / E[0]),
            "dT_pct_K20": float(100 * (M[i20] - M[0]) / M[0])}


# ---------------------------------------------------------------- campaign

def stream_columns(J: int = STREAM_J):
    """(submit, runtime, procs) of the synthetic SWF campaign stream."""
    return synthetic_swf_arrays(J, seed=STREAM_SEED)


def _prefix(cols, J: int):
    return workload_from_arrays(*(c[:J] for c in cols), JSCC_SYSTEMS)


def phase_campaign(cols, chunk: int = CHUNK) -> dict:
    """The million-job campaign: 4 K x 2 seeds over the whole stream,
    chunked, totals only, auto placer.  On the chip the compiled step
    must hold the Pallas kernel.  Warm profile tables and no faults make
    the seed lanes identical, which is checked bit for bit."""
    w = _prefix(cols, len(cols[0]))
    sched = Scheduler(_k_grid([0.0, 0.1, 0.2, 0.3]), warm_start=True,
                      seeds=[0, 1], chunk=chunk)
    step = sched.lower(w, totals_only=True).compile().as_text()
    pallas = "tpu_custom_call" in step
    check(pallas or jax.default_backend() != "tpu",
          "campaign: the compiled chunk step holds no Pallas kernel")
    res = sched.run(w, totals_only=True)
    E = np.asarray(res.total_energy)
    M = np.asarray(res.makespan)
    W = np.asarray(res.total_wait)
    check(E.shape == (4, 2) and _finite(E, M, W), "campaign: bad totals")
    check(E[:, 0].tobytes() == E[:, 1].tobytes()
          and W[:, 0].tobytes() == W[:, 1].tobytes(),
          "campaign: identical seed lanes differ")
    return {"jobs": len(w.prog), "lanes": 8, "chunk": chunk,
            "pallas_kernel_in_step": pallas,
            "energy_J": E[:, 0].tolist(), "makespan_s": M[:, 0].tolist(),
            "mean_wait_s": (W[:, 0] / len(w.prog)).tolist()}


# ------------------------------------------------------------- backfilling

def phase_backfilling(cols, J: int = 10**5, cap: float = 52000.0) -> dict:
    """The stream's first J jobs under EASY (window 16), then conservative
    (window 8) under a power cap, each on a 2-lane K grid."""
    w = _prefix(cols, J)
    out = {"jobs": J}
    for name, queue, pc in (("easy", "easy_backfill:window=16", None),
                            ("conservative", "conservative:window=8", cap)):
        res = Scheduler(_k_grid([0.0, 0.2]), warm_start=True, queue=queue,
                        power_cap=pc).run(w, totals_only=True)
        E = np.asarray(res.total_energy)
        M = np.asarray(res.makespan)
        nbf = np.asarray(res.n_backfilled)
        check(E.shape == (2,) and _finite(E, M), f"{name}: bad totals")
        check(np.all(nbf > 0), f"{name}: nothing was backfilled")
        row = {"energy_J": E.tolist(), "makespan_s": M.tolist(),
               "n_backfilled": nbf.tolist()}
        if pc is not None:
            peak = np.asarray(res.peak_power)
            check(np.all(peak <= pc * (1 + 1e-6)),
                  f"{name}: peak power {peak} over the {pc} W cap")
            row["peak_power_W"] = peak.tolist()
        out[name] = row
    return out


# --------------------------------------------------------------- agreement

#: queue spec -> the same discipline as a mirror SimConfig
AGREEMENT_QUEUES = {
    "fcfs": {},
    "easy_backfill:window=16": dict(queue="easy_backfill", queue_window=16),
    "conservative:window=8": dict(queue="conservative", queue_window=8),
}


def phase_agreement(cols, J: int = 10**4, seed: int = 0) -> dict:
    """The engine on the chip against the float64 mirror, with
    tests/test_differential_scale.py's tolerances: placements exact,
    totals to MIRROR_RTOL; conservative's realization order may flip on
    f32 ties, so its backfill flags get a count band and its wait sum a
    wider one.  Then the kth-free placer in its auto mode (the compiled
    Pallas kernel on the chip) against the sort reference, bit for bit."""
    w = _prefix(cols, J)
    out = {"jobs": J}
    for spec, over in AGREEMENT_QUEUES.items():
        cfg = SimConfig(mode="paper", k=0.1, warm_start=True, **over)
        rj = Scheduler(cfg.policy(), warm_start=True).run(w)
        rp = simulate_py(w, cfg)
        tie_order = over.get("queue") == "conservative"
        sys_j = np.asarray(rj.system)
        n_diff = int(np.sum(sys_j != rp["system"]))
        check(n_diff == 0, f"{spec}: {n_diff} placements differ from the "
              f"mirror")
        bf_j = np.asarray(rj.backfilled)
        if tie_order:
            check(abs(int(rj.n_backfilled) - rp["n_backfilled"])
                  <= max(16, J // 100), f"{spec}: backfill count "
                  f"{int(rj.n_backfilled)} vs mirror {rp['n_backfilled']}")
        else:
            check(np.array_equal(bf_j, rp["backfilled"]),
                  f"{spec}: backfill flags differ from the mirror")
        for f, rtol, atol in (
                ("total_energy", MIRROR_RTOL, 0.0),
                ("makespan", MIRROR_RTOL, 0.0),
                ("total_wait", 5e-3 if tie_order else MIRROR_RTOL, 1.0)):
            a, b = float(getattr(rj, f)), float(rp[f])
            check(abs(a - b) <= atol + rtol * abs(b),
                  f"{spec}: {f} {a} vs mirror {b}")
        out[spec] = {"n_backfilled": int(rj.n_backfilled),
                     "energy_J": float(rj.total_energy),
                     "mirror_energy_J": float(rp["total_energy"])}

    # half-second free times on a short range: many ties, as on a real
    # node table; systems 1.. padded past node 100 as the engine pads
    rng = np.random.default_rng(seed)
    free = rng.integers(0, 200, (17, 4, 136)).astype(np.float32) * 0.5
    free[:, 1:, 100:] = BIG
    n_req = rng.integers(1, 137, (17, 4)).astype(np.int32)
    one = kth_free_time(free[0], n_req[0])
    check(np.asarray(one).tobytes()
          == np.asarray(kth_free_time(free[0], n_req[0], force="sort"))
          .tobytes(), "kth_free [4,136]: auto placer != sort")
    many = kth_free_time_batched(free, n_req)
    check(np.asarray(many).tobytes()
          == np.asarray(kth_free_time_batched(free, n_req, force="sort"))
          .tobytes(), "kth_free [17,4,136]: auto placer != sort")
    out["kth_free_placer"] = ("pallas" if jax.default_backend() == "tpu"
                              else "jnp")
    return out


# ----------------------------------------------------------------- service

def _service_stream(J: int, seed: int = 5):
    return make_stream_workload(JSCC_SYSTEMS, J, arrival="diurnal",
                                rate=0.05, seed=seed)


def _check_same_decisions(live, batch, what: str):
    for f in ("system", "start", "finish", "wait", "backfilled",
              "total_energy", "makespan", "total_wait"):
        check(np.asarray(getattr(live, f)).tobytes()
              == np.asarray(getattr(batch, f)).tobytes(),
              f"{what}: {f} differs from the event-core batch run")


def _replay(w, send, envelopes):
    """Submit-before-drive-past over a JSONL handler: every session (one
    ``envelopes`` entry each) gets the whole stream, a drive without an
    envelope advances them all, and one what-if goes to the first half
    way.  Returns every reply."""
    replies = []
    for j in range(len(w.prog)):
        t = float(w.arrival[j])
        prog = w.programs[int(w.prog[j])]
        replies.append(send({"op": "drive", "until": t}))
        for env in envelopes:
            replies.append(send({"op": "submit", **env, "prog": prog,
                                 "arrival": t}))
        if j == len(w.prog) // 2:
            replies.append(send({"op": "whatif", **envelopes[0],
                                 "prog": w.programs[0]}))
    return replies


def phase_service(out_dir, J: int = 300, n_pool: int = 8) -> dict:
    """The live service in-process: one session through ``handle`` and
    an ``n_pool``-session pool through ``handle_pool`` take the same
    diurnal stream, interleaved with drives and one what-if; each is
    checkpointed, restored into a fresh instance and drained.  The
    restored session's decisions must equal the event-core batch run,
    and every reply must carry ``ok``."""
    out_dir = pathlib.Path(out_dir)
    shutil.rmtree(out_dir, ignore_errors=True)
    w = _service_stream(J)
    pol = make_policy("paper", k=0.1)

    sched = Scheduler(pol, warm_start=True)
    batch = Scheduler(pol, warm_start=True, engine="events").run(w)
    mk = dict(capacity=J, checkpoint_dir=str(out_dir / "single"))
    disp = Dispatcher.from_scheduler(sched, w, **mk)
    replies = _replay(w, lambda r: handle(disp, r), [{}])
    replies.append(handle(disp, {"op": "checkpoint"}))
    fresh = Dispatcher.from_scheduler(sched, w, **mk)
    replies.append(handle(fresh, {"op": "restore"}))
    check(replies[-1].get("resumed") is True, "service: restore found no "
          "checkpoint")
    replies.append(handle(fresh, {"op": "drain"}))
    replies.append(handle(fresh, {"op": "result"}))
    bad = [r for r in replies if not r.get("ok")]
    check(not bad, f"service: {len(bad)} replies without ok: {bad[:3]}")
    _check_same_decisions(fresh.result(), batch, "service (one session)")
    n_dec = len(fresh.decisions)
    check(n_dec == J, f"service: {n_dec} decisions for {J} jobs")

    ks = np.linspace(0.0, 0.35, n_pool).astype(np.float32)
    scheds = [Scheduler(make_policy("paper", k=float(k)), warm_start=True)
              for k in ks]
    pk = dict(capacity=J, checkpoint_dir=str(out_dir / "pool"))
    pool = SessionPool(scheds, w, **pk)
    try:
        preplies = _replay(w, lambda r: handle_pool(pool, r),
                           [{"session": i} for i in range(n_pool)])
        preplies.append(handle_pool(pool, {"op": "checkpoint"}))
    finally:
        pool.close()
    pool2 = SessionPool(scheds, w, **pk)
    try:
        preplies.append(handle_pool(pool2, {"op": "restore"}))
        check(preplies[-1].get("resumed") is True, "service: pool restore "
              "found no checkpoint")
        preplies.append(handle_pool(pool2, {"op": "drain"}))
        bad = [r for r in preplies if not r.get("ok")]
        check(not bad, f"pool: {len(bad)} replies without ok: {bad[:3]}")
        for i, s in enumerate(scheds):
            ref = Scheduler(s.policy, warm_start=True,
                            engine="events").run(w)
            _check_same_decisions(pool2.result(i), ref, f"pool session {i}")
        pool_steps = pool2.n_pool_steps
    finally:
        pool2.close()
    return {"jobs": J, "sessions": 1 + n_pool,
            "replies": len(replies) + len(preplies),
            "energy_J": float(batch.total_energy),
            "pool_steps_after_restore": pool_steps}


# -------------------------------------------------------------- four chips

def phase_four_chips(cols, chunk: int = CHUNK, shards: int = 4) -> dict:
    """The sharded campaign grid (16 lanes: 8 K x 2 seeds over the whole
    stream) on ``shards`` devices against the same grid on one device:
    totals bit-identical, and every result leaf spread over ``shards``
    distinct devices."""
    w = _prefix(cols, len(cols[0]))
    pol = _k_grid(np.linspace(0.0, 0.35, 8))

    def run(n):
        return Scheduler(pol, warm_start=True, seeds=[0, 1], chunk=chunk,
                         shards=n).run(w, totals_only=True)

    many, one = run(shards), run(1)
    fields = ("total_energy", "makespan", "total_wait", "max_wait",
              "slowdown_sum", "busy", "idle_energy")
    for f in fields:
        a, b = getattr(many, f), getattr(one, f)
        check(np.asarray(a).tobytes() == np.asarray(b).tobytes(),
              f"four chips: {f} differs between shards={shards} and 1")
        devs = a.sharding.device_set
        check(len(devs) == shards, f"four chips: {f} lives on "
              f"{len(devs)} devices, not {shards}")
    E = np.asarray(many.total_energy)
    check(E.shape == (8, 2) and _finite(E), "four chips: bad totals")
    return {"jobs": len(w.prog), "lanes": 16, "chunk": chunk,
            "shards": shards, "bit_identical": True,
            "result_devices": len(many.total_energy.sharding.device_set),
            "energy_J": E[:, 0].tolist()}


# -------------------------------------------------------------------- main

class SetupClock:
    """JAX's own lowering and compile durations and persistent-cache hits,
    read from jax.monitoring (the listeners live as long as the
    process; create one per run).  Tracing is left out: nested jits
    report overlapping trace spans."""
    COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                      "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event in self.COMPILE_EVENTS:
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def phase(self, name, fn, *args, **kw) -> dict:
        c0, h0, t0 = self.compile_s, self.cache_hits, time.perf_counter()
        info = fn(*args, **kw)
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        print(json.dumps({"phase": name, "smoke_timing": {
            "compile_s": comp, "warm_s": wall - comp,
            "cache_hits": self.cache_hits - h0}, **info}), flush=True)
        return info


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way sharded campaign and its "
                         "one-device twin (needs 4 chips)")
    args = ap.parse_args(argv)

    dev = phase_device(4 if args.four_chips else 1)
    print(json.dumps({"phase": "device", **dev}), flush=True)
    cache = enable_compile_cache()
    print(json.dumps({"compile_cache": cache}), flush=True)
    clock = SetupClock()
    cols = stream_columns()
    if args.four_chips:
        clock.phase("four_chips", phase_four_chips, cols)
    else:
        clock.phase("paper", phase_paper)
        clock.phase("campaign", phase_campaign, cols)
        clock.phase("backfilling", phase_backfilling, cols)
        clock.phase("agreement", phase_agreement, cols)
        clock.phase("service", phase_service,
                    ROOT / "chiprun_out" / "chip_smoke")
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
