"""Rigid-job campaign traffic: a planner's (fault x K) grid over a trace-
shaped stream whose jobs keep their own runtimes and widths, replayed
again and again through ``Scheduler.run``.

The campaign kind (``bench/traffic/campaign.py``) with the stream of
``bench/traffic/rigid.py`` in place of the NPB stream.  Parameters
(``bench/traffic/<mix>.json``, ``"kind": "campaign-rigid"``) are the
campaign kind's, with ``shape`` (the rigid-job shape) in place of
``mix``; ``arrivals.rate_per_s`` is the mean rate at which the stream
offers ``offered_load`` of the facility's node-seconds (each job counted
on its fastest system; ``bench/configs/build_cori2.py`` prints the
arithmetic).

What ``correct`` compares (``Cell.gaps``): the learned tables run per
class while each job's runtime is its own, so every lane's running means
drift in float32 and its decisions come within that drift of a tie, as
the campaign kind's straggler lanes do.  Every lane therefore compares
the sums in ``totals`` (``totals_gap``), and the share of its jobs that
it placed on another (class, system) cell than the reference did
(``placement_gap``), each against its limit.
"""

from __future__ import annotations

from dataclasses import replace

import numpy as np

from bench import harness
from bench.reference.common import (idle_energy, placement_gap, rel_gap,
                                    same, straggler_factors)
from bench.reference.fcfs_rigid import fcfs_rigid
from bench.traffic import campaign, rigid, streams


class Cell(campaign.Cell):
    def setup(self):
        from repro.core import FaultConfig, Scheduler, make_policy
        tr = self.tr
        if self.cfg["queue"] != "fcfs" or self.cfg.get("power_cap_w"):
            raise SystemExit("campaign-rigid: the reference states the "
                             "uncapped FCFS queue only")
        self.J = int(tr["jobs"])
        with harness.span("campaign.prepare"):
            (self.prog, self.arrival, self.t_scale, self.n_job, _,
             _) = rigid.rigid_stream(streams.rng_for(self.seed), self.J, tr,
                                     self.tables)
            # a program without per-job columns refuses these: it cannot
            # run this traffic
            self.w = replace(
                campaign.workload(self.tables, self.prog, self.arrival),
                t_scale=self.t_scale, n_req_job=self.n_job)
        self.ks = [float(k) for k in tr["k_grid"]]
        self.faults = [dict(f) for f in tr["faults"]]
        R = int(tr["seeds_per_point"])
        seeds = campaign.lane_seeds(self.seed, R)
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

    def reference(self, rnd=same) -> dict:
        lanes = self.grid()
        draws = {}
        for f_i, _, r_i in lanes:
            if (f_i, r_i) not in draws:
                fault = self.faults[f_i]
                draws[f_i, r_i] = straggler_factors(
                    self.seeds[r_i], self.J, fault["straggler_prob"],
                    fault["straggler_factor"])
        ref = fcfs_rigid(self.tables, self.prog, self.arrival, self.t_scale,
                         self.n_job, [self.ks[g] for _, g, _ in lanes],
                         np.stack([draws[f, r] for f, _, r in lanes]),
                         rnd=rnd)
        ref["idle_energy"] = idle_energy(self.tables, ref["makespan"],
                                         ref["busy"])
        return ref

    def gaps(self, got: dict, ref: dict) -> dict:
        """Every number this kind compares (see the module's docstring),
        and each field's gap in ``per_field``."""
        per = {k: rel_gap(got[k], ref[k]) for k in self.tr["totals"]}
        per = {k: v if v == v else float("inf") for k, v in per.items()}
        self.per_field = {"totals_gap": per}
        return {"totals_gap": max(per.values()),
                "placement_gap": placement_gap(got["runs"], ref["runs"],
                                               self.J)}
