"""Campaign traffic: a planner's whole (fault x K x seed) grid over one
job stream, replayed again and again through ``Scheduler.run``.

Parameters (``bench/traffic/<mix>.json``, ``"kind": "campaign"``):

- ``jobs``, ``arrivals``, ``mix``: the NPB stream (``streams.npb_stream``);
- ``k_grid``: the paper's K values, one lane each;
- ``faults``: ``[{"straggler_prob": p, "straggler_factor": f}, ...]``,
  one lane group each;
- ``seeds_per_point``: fault-draw seeds per (fault, K) point (1: no seed
  axis);
- ``shards``: devices the grid is split over (null: one device);
- ``totals``: the result fields that ``totals_gap`` compares in the
  fault-free lanes; ``steady_totals``: those that ``totals_gap.straggler``
  compares in the straggler lanes (see ``Cell.gaps``);
- ``limits``: the numbers compared (of ``totals_gap``,
  ``placement_gap`` and the same with ``.straggler``) and the limit of
  each.

The window repeats whole campaigns and closes at the end of the first
one that ends at or after ``--seconds`` (two at the least).  Every
campaign of a run computes the same grid, so each must return the same
totals.  A traced run traces ``TRACE_S`` seconds from the start of the
second campaign: a whole campaign holds millions of device operations,
too many to trace and read back in a run's time.
"""

from __future__ import annotations

import time

import numpy as np

from bench import harness
from bench.reference.common import (idle_energy, placement_gap, rel_gap,
                                    same, straggler_factors)
from bench.reference.fcfs import fcfs
from bench.traffic import streams

#: seconds of the second campaign that a traced run traces
TRACE_S = 1.0


def workload(tables: dict, prog, arrival):
    """The program's ``Workload`` over the frozen facility tables."""
    from repro.core.engine import Workload
    J = len(prog)
    f = lambda k: np.asarray(tables[k], np.float64)
    return Workload(
        prog=np.asarray(prog, np.int32), arrival=np.asarray(arrival,
                                                           np.float32),
        k_job=np.full(J, np.nan, np.float32),
        n_req=np.asarray(tables["n_req"], np.int32),
        T_true=f("T_true"), C_true=f("C_true"), E_true=f("E_true"),
        T_pred=f("T_true"), C_pred=f("C_true"),
        n_nodes=np.asarray(tables["n_nodes"], np.int32),
        programs=tuple(tables["programs"]), systems=tuple(tables["systems"]),
        idle_w=np.asarray(tables["idle_w"], np.float32),
        T_comp=f("T_comp"), E_comp=f("E_comp"))


def lane_seeds(seed: int, n: int) -> list[int]:
    """Fault-draw seeds of the grid, from the run seed (int32 range)."""
    rng = streams.rng_for(seed, 1)
    return [int(s) for s in rng.integers(0, 2**31 - 1, n)]


class Cell:
    def __init__(self, cell: dict, seed: int):
        self.seed = seed
        self.cfg = cell["config_data"]
        self.tr = cell["traffic_data"]
        self.tables = self.cfg["tables"]

    # ----------------------------------------------------------- set-up
    def setup(self):
        from repro.core import FaultConfig, Scheduler, make_policy
        tr = self.tr
        if self.cfg["queue"] != "fcfs" or self.cfg.get("power_cap_w"):
            raise SystemExit("campaign: the reference states the uncapped "
                             "FCFS queue only")
        self.J = int(tr["jobs"])
        with harness.span("campaign.prepare"):
            self.prog, self.arrival = streams.npb_stream(
                streams.rng_for(self.seed), self.J, tr,
                self.tables["programs"])
            self.w = workload(self.tables, self.prog, self.arrival)
        self.ks = [float(k) for k in tr["k_grid"]]
        self.faults = [dict(f) for f in tr["faults"]]
        R = int(tr["seeds_per_point"])
        seeds = lane_seeds(self.seed, R)
        self.seeds = seeds if R > 1 else seeds[:1]
        self.lanes = len(self.faults) * len(self.ks) * R
        pol = make_policy(self.cfg["policy"]["name"]).with_params(
            k=np.asarray(self.ks, np.float32))
        self.sched = Scheduler(
            pol, warm_start=bool(self.cfg["policy"]["warm_start"]),
            queue=self.cfg["queue"],
            faults=tuple(FaultConfig(straggler_prob=f["straggler_prob"],
                                     straggler_factor=f["straggler_factor"])
                         for f in self.faults),
            seeds=self.seeds if R > 1 else self.seeds[0],
            shards=tr.get("shards"))
        self.shards = int(tr.get("shards") or 1)
        self.first = self._campaign()          # compiles: set-up

    def _campaign(self, tracer=None):
        import jax
        with harness.span("campaign.run"):
            res = self.sched.run(self.w, totals_only=True)
            if tracer is not None:
                tracer.start()
                # the outer span began before the trace: this one lands in it
                with harness.span("campaign.run"):
                    time.sleep(TRACE_S)
                tracer.stop()
            jax.block_until_ready(res.total_energy)
        with harness.span("campaign.fetch"):
            out = {k: np.asarray(getattr(res, k))
                   for k in list(self.tr["totals"]) + ["runs"]}
        return out

    # ----------------------------------------------------------- window
    def measure(self, seconds: float, tracer) -> dict:
        n, bad = 0, 0
        walls = []
        t0 = time.perf_counter()
        while True:
            t_c = time.perf_counter()
            out = self._campaign(tracer if tracer.on and n == 1 else None)
            walls.append(time.perf_counter() - t_c)
            n += 1
            bad += sum(not np.array_equal(out[k], self.first[k])
                       for k in out)
            if n >= 2 and time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.last = out
        job_lanes = self.J * self.lanes
        return {
            "metrics": {"campaign_job_lanes_per_s": job_lanes * n / elapsed},
            "attempted": n * self.lanes,
            "failed": 0 if bad == 0 else n * self.lanes,
            "counters": {"lanes_per_device": self.lanes // self.shards,
                         "systems": len(self.tables["n_nodes"]),
                         "max_nodes": max(self.tables["n_nodes"])},
            "notes": [f"campaigns in the window: {n}, {elapsed:.3f} s, "
                      f"{self.lanes} lanes x {self.J} jobs; campaigns that "
                      f"differ from the first: {bad}",
                      "campaign wall s: " + " ".join(f"{w:.4f}"
                                                     for w in walls)],
        }

    def free(self):
        self.sched = None
        self.w = None

    # ------------------------------------------------------------ check
    def grid(self) -> list[tuple]:
        """(fault, K, seed) index triples of every lane, in the grid's
        order."""
        return [(f, g, r) for f in range(len(self.faults))
                for g in range(len(self.ks)) for r in range(len(self.seeds))]

    def reference(self, rnd=same) -> dict:
        """The plain reference's totals for every lane, lane first."""
        lanes = self.grid()
        draws = {}
        for f_i, _, r_i in lanes:
            if (f_i, r_i) not in draws:
                fault = self.faults[f_i]
                draws[f_i, r_i] = straggler_factors(
                    self.seeds[r_i], self.J, fault["straggler_prob"],
                    fault["straggler_factor"])
        ref = fcfs(self.tables, self.prog, self.arrival,
                   [self.ks[g] for _, g, _ in lanes],
                   np.stack([draws[f, r] for f, _, r in lanes]), rnd=rnd)
        ref["idle_energy"] = idle_energy(self.tables, ref["makespan"],
                                         ref["busy"])
        return ref

    def gaps(self, got: dict, ref: dict) -> dict:
        """Every number this kind compares, and each field's gap (for
        the look at a high reading).

        Fault-free lanes learn from identical observations, so their
        tables stay at the truth and every decision keeps the tables'
        own margin (1e-2 or more): their placements compare exactly and
        every field of ``totals``.  Straggler lanes' running means move,
        and their decisions come within float32's drift of a tie (1e-5
        and closer), where one flip moves a cascade of jobs and of waits:
        they compare the sums of ``steady_totals`` (suffix
        ``.straggler``); their ``placement_gap.straggler`` is read, and
        compared only where ``limits`` names it."""
        exact = np.array([self.faults[f]["straggler_prob"] == 0
                          for f, _, _ in self.grid()])
        out, self.per_field = {}, {}
        for tag, sel, fields in (("", exact, self.tr["totals"]),
                                 (".straggler", ~exact,
                                  self.tr["steady_totals"])):
            if not sel.any():
                continue
            per = {k: rel_gap(got[k][sel], ref[k][sel]) for k in fields}
            per = {k: v if v == v else float("inf") for k, v in per.items()}
            self.per_field["totals_gap" + tag] = per
            out["totals_gap" + tag] = max(per.values())
            out["placement_gap" + tag] = placement_gap(
                got["runs"][sel], ref["runs"][sel], self.J)
        return out

    def check(self) -> list[dict]:
        lanes = len(self.grid())
        shape = (len(self.faults), len(self.ks)) + (
            (len(self.seeds),) if len(self.seeds) > 1 else ())
        lim = self.tr["limits"]
        if any(v.shape[:len(shape)] != shape for v in self.last.values()):
            worst = {n: float("inf") for n in lim}
        else:
            got = {k: v.reshape((lanes,) + v.shape[len(shape):])
                   for k, v in self.last.items()}
            worst = self.gaps(got, self.reference())
        return [{"name": n, "value": worst[n], "limit": lim[n]}
                for n in lim]

    def control(self) -> dict:
        """The numbers compared when the reference, computed in bfloat16,
        stands in the program's place."""
        from bench.reference.common import bf16
        return self.gaps(self.reference(rnd=bf16), self.reference())
