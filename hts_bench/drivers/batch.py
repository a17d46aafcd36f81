"""Closed-loop batch driver: back-to-back ``hts.run_many`` or ``hts.sweep``
calls, the way an architect runs a population study or a design sweep.

The traffic file's ``entry`` picks the call.  ``run_many`` cycles through
the generated populations; ``sweep`` repeats the one FU sweep over the
paper's programs.  A window's rate is every scenario of every call over
the time from the first call's start to the last call's end: the window
runs whole calls, and the last starts before ``seconds`` have passed.
Where the traffic gives each program a scheduling policy, a ``run_many``
call passes its lanes' policies, and the check gives each lane its own.
"""
from __future__ import annotations

import time

import numpy as np

from hts_bench import common, gen


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 spans: common.Spans):
        self.cfg, self.t, self.seed = cfg, traffic, seed
        self.seconds = seconds
        self.spans = spans
        self.entry = traffic["entry"]
        self.calls: list = []        # (call index into traffic.calls, result)
        self.call_s = 0.0            # host wall of the window's calls

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        from repro.core import hts
        with self.spans("bench.generate"):
            self.traffic = gen.generate(self.seed, self.t, self.seconds)
        self.params = common.hts_params(self.cfg, self.t)
        progs = self.traffic.programs
        self.max_prog = max(self.t.get("max_prog", 16),
                            *(hts.prog_bucket(len(p.code)) for p in progs))
        pols = self.traffic.policies
        self.policies = None if pols is None else [
            hts.SchedPolicy.of(**(d or {})) for d in pols]
        # warm-up: the one shape every call has (lanes, program table),
        # filled with an empty program, which halts on the first trip
        empty = hts.Program("warm_up").build()
        self._call([empty] * len(self.traffic.calls[0]))

    def _call(self, progs, policies=None):
        from repro.core import hts
        kw = {} if policies is None else {"policy": policies}
        with self.spans("bench.call"):
            if self.entry == "run_many":
                return hts.run_many(progs, scheduler=self.cfg["scheduler"],
                                    n_fu=self.cfg["n_fu"],
                                    params=self.params,
                                    max_prog=self.max_prog, check=False,
                                    **kw)
            return hts.sweep(progs, n_fu=self.t["n_fu"],
                             schedulers=self.t["schedulers"],
                             params=self.params, max_prog=self.max_prog,
                             max_fu_per_class=self.t["max_fu_per_class"])

    # -- the measured window --------------------------------------------------
    def window(self, seconds: float) -> dict:
        n_calls = len(self.traffic.calls)
        t0 = time.perf_counter()
        while not self.calls or time.perf_counter() - t0 < seconds:
            i = len(self.calls) % n_calls
            idx = self.traffic.calls[i]
            progs = [self.traffic.programs[k] for k in idx]
            pols = (None if self.policies is None
                    else [self.policies[k] for k in idx])
            t_call = time.perf_counter()
            self.calls.append((i, self._call(progs, pols)))
            self.call_s += time.perf_counter() - t_call
        wall = time.perf_counter() - t0
        lanes = sum(self._lanes(res) for _, res in self.calls)
        failed = sum(int((~np.asarray(res.halted)).sum())
                     for _, res in self.calls if self.entry == "run_many")
        return {"attempted": lanes, "failed": failed,
                "metrics": {"scenarios_per_s": lanes / wall},
                "notes": [f"window: calls={len(self.calls)} "
                          f"scenarios={lanes} wall_s={wall!r}"]}

    def _lanes(self, res) -> int:
        if self.entry == "run_many":
            return len(res)
        return sum(int(np.asarray(c).size) for c in res.cycles.values())

    # -- per-layer counters -----------------------------------------------------
    def counters(self) -> dict:
        """The window's calls: their host wall, and the part of it the
        program itself timed around its device launches (``wall_us``:
        arguments to the device, the machine's run, results back).  For
        ``run_many`` calls also the machine steps: per call, the while-loop
        trips (its slowest lane's steps) and the steps of every lane;
        ``hts.sweep`` returns cycles only, so a sweep has no steps."""
        walls = [res.wall_us if self.entry == "run_many"
                 else sum(res.wall_us.values()) for _, res in self.calls]
        out = {"calls": len(self.calls), "call_s": self.call_s,
               "launch_s": 1e-6 * sum(walls)}
        if self.entry != "run_many":
            return out
        trips = lane_steps = lane_trips = 0
        for _, res in self.calls:
            steps = np.asarray(res.steps)
            trips += int(steps.max())
            lane_steps += int(steps.sum())
            lane_trips += steps.size * int(steps.max())
        return dict(out, trips=trips, lane_steps=lane_steps,
                    lane_trips=lane_trips)

    def release(self) -> None:
        """Nothing to free: ``run_many`` and ``sweep`` hand back host
        arrays, and the check reads only those."""

    # -- the comparison with the reference ----------------------------------------
    def check(self) -> list:
        if self.entry == "run_many":
            return self._check_population()
        return self._check_sweep()

    def _repeats(self, key) -> int:
        """Calls whose answers differ from the first call of the same
        inputs (the machine is deterministic)."""
        first: dict = {}
        bad = 0
        for i, res in self.calls:
            a = key(res)
            if i not in first:
                first[i] = a
            elif any(not np.array_equal(x, y) for x, y in zip(a, first[i])):
                bad += 1
        return bad

    def _check_population(self) -> list:
        rng = np.random.default_rng([self.seed, 7])
        flat = [(c, lane) for c, (_, res) in enumerate(self.calls)
                for lane in range(len(res))]
        steps = np.concatenate([np.asarray(res.steps)
                                for _, res in self.calls])
        unhalted = sum(int((~np.asarray(res.halted)).sum())
                       for _, res in self.calls)
        bad = 0
        for j in common.sample(rng, len(flat), self.t["check_lanes"],
                               must=int(np.argmax(steps))):
            c, lane = flat[j]
            i, res = self.calls[c]
            got = res[lane]
            k = self.traffic.calls[i][lane]
            pol = (None if self.traffic.policies is None
                   else self.traffic.policies[k])
            want = common.expect(self.traffic.programs[k], self.cfg,
                                 self.cfg["scheduler"], self.cfg["n_fu"],
                                 policy=pol)
            bad += common.differs(got.cycles, got.halted,
                                  got.schedule_tuple(), want)
        return [
            common.Check("lanes_unhalted", unhalted, 0),
            common.Check("calls_differing_on_repeat",
                         self._repeats(lambda r: (np.asarray(r.cycles),
                                                  np.asarray(r.steps))), 0),
            common.Check("lanes_differing_from_reference", bad, 0),
        ]

    def _check_sweep(self) -> list:
        rng = np.random.default_rng([self.seed, 7])
        scheds, points = self.t["schedulers"], self.t["n_fu"]
        i, res = self.calls[-1]
        progs = [self.traffic.programs[k] for k in self.traffic.calls[i]]
        # every (scheduler, program, FU point) answer of the last call
        flat = [(s, p, f) for s in scheds for p in range(len(progs))
                for f in range(len(points))]
        cyc = [int(res.cycles[s][p, f]) for s, p, f in flat]
        longest = int(np.argmax(cyc))
        bad = 0
        for j in common.sample(rng, len(flat), self.t["check_points"],
                               must=longest):
            s, p, f = flat[j]
            want = common.expect(progs[p], self.cfg, s, points[f])
            bad += common.differs(cyc[j], True, None, want)
        return [
            common.Check("calls_differing_on_repeat",
                         self._repeats(lambda r: tuple(
                             np.asarray(r.cycles[s]) for s in scheds)), 0),
            common.Check("points_differing_from_reference", bad, 0),
        ]
