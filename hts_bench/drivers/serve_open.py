"""Open-loop serving driver: Poisson arrivals into one ``hts.serve`` server.

The server is single-threaded and launches inside ``submit``/``poll``, so
the client is the same thread: whenever control comes back it submits
every request that has fallen due and polls.  Time to result runs from the
moment a request was due, so a launch that holds the thread delays the
requests that fall due meanwhile, and that delay is counted.  How late the
client submitted goes on an earlier line.  A request the server refuses
(``QueueFullError``) counts as failed.
"""
from __future__ import annotations

import time

import numpy as np

from hts_bench import common, gen


def _totals(report) -> dict:
    """Per bucket: launches, requests, and the sums behind the report's
    means (launch-weighted occupancy, request-weighted wait)."""
    return {k: (b.batches, b.requests, b.occupancy * b.batches,
                b.mean_wait * b.requests)
            for k, b in report.per_bucket.items()}


def _backlog(due, done_at, refused, seconds) -> tuple:
    """Requests due and not yet finished (nor refused) over the window:
    the least-squares growth per second over its second half, sampled
    every 0.1 s, and the counts at its four quarter marks.  A rate the
    server sustains keeps the growth near 0."""
    grid = np.arange(0.1, seconds + 1e-9, 0.1)
    done = np.sort(done_at[~np.isnan(done_at)])
    backlog = (np.searchsorted(due, grid, side="right")
               - np.searchsorted(done, grid, side="right")
               - np.searchsorted(np.sort(due[refused]), grid, side="right"))
    half = grid >= seconds / 2
    growth = (float(np.polyfit(grid[half], backlog[half], 1)[0])
              if half.sum() >= 2 else 0.0)
    quarters = [int(backlog[np.searchsorted(grid, seconds * q + 1e-9) - 1])
                for q in (0.25, 0.5, 0.75, 1.0)]
    return growth, quarters


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 spans: common.Spans):
        self.cfg, self.t, self.seed = cfg, traffic, seed
        self.seconds = seconds
        self.spans = spans
        self.served: dict = {}       # arrival index -> Result
        self.unanswered = 0

    def _spec(self):
        from repro.core import hts
        return hts.ServeSpec(scheduler=self.cfg["scheduler"],
                             n_fu=self.cfg["n_fu"],
                             params=common.hts_params(self.cfg, self.t),
                             **self.t["serve"])

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        from repro.core import hts
        with self.spans("bench.generate"):
            self.traffic = gen.generate(self.seed, self.t, self.seconds)
        self.srv = hts.serve(self._spec())
        lanes = self.t["serve"]["max_batch"]
        buckets: dict = {}
        for p in self.traffic.programs:
            buckets.setdefault(self.srv.bucket_of(p), []).append(p)
        # every bucket: a full launch that harvests and refills lanes;
        # then every partial launch width once (pad lanes retire on entry)
        for progs in buckets.values():
            self._serve_now([progs[k % len(progs)]
                             for k in range(2 * lanes + 1)])
        first = next(iter(buckets.values()))
        for n in range(1, lanes):
            self._serve_now(first[:1] * n)
        self.before = _totals(self.srv.report())

    def _serve_now(self, progs) -> None:
        futs = [self.srv.submit(p) for p in progs]
        self.srv.drain()
        for f in futs:
            f.result(timeout=0)

    # -- the measured window --------------------------------------------------
    def window(self, seconds: float) -> dict:
        from repro.core import hts
        due, pick = self.traffic.arrivals, self.traffic.requests
        progs = self.traffic.programs
        n = len(due)
        done_at = np.full(n, np.nan)
        late = np.zeros(n)
        futs: dict = {}
        refused: list = []
        clock = time.perf_counter
        t0 = clock()

        def submit(k):
            with self.spans("bench.submit"):
                late[k] = clock() - t0 - due[k]
                try:
                    f = self.srv.submit(progs[pick[k]])
                except hts.QueueFullError:
                    refused.append(k)
                    return
            f.add_done_callback(
                lambda _, k=k: done_at.__setitem__(k, clock() - t0))
            futs[k] = f

        k = 0
        while clock() - t0 < seconds:
            while k < n and due[k] <= clock() - t0:
                submit(k)
                k += 1
            with self.spans("bench.poll"):
                self.srv.poll()
            nxt = due[k] if k < n else seconds
            pause = min(nxt - (clock() - t0), 0.001)
            if pause > 0:
                time.sleep(pause)
        while k < n:                 # due inside the window, not yet sent
            submit(k)
            k += 1
        with self.spans("bench.drain"):
            self.srv.drain()
        for k, f in futs.items():
            if f.done() and f.exception() is None:
                self.served[k] = f.result()
            else:
                self.unanswered += 1
        ttr = np.asarray([done_at[k] - due[k] for k in self.served]) * 1e3
        in_window = int(np.sum(done_at <= seconds))
        self.after = _totals(self.srv.report())
        sent = late[list(futs)] * 1e3 if futs else np.zeros(1)
        growth, quarters = _backlog(due, done_at, refused, seconds)
        return {
            "attempted": n, "failed": len(refused) + self.unanswered,
            "metrics": {"scenarios_per_s": in_window / seconds,
                        "ttr_p50_ms": float(np.percentile(ttr, 50)),
                        "ttr_p95_ms": float(np.percentile(ttr, 95))},
            "notes": [f"window: due={n} served={len(self.served)} "
                      f"refused={len(refused)} done_in_window={in_window} "
                      f"backlog_at_close={n - len(refused) - in_window} "
                      f"backlog_at_quarters={quarters} "
                      f"backlog_growth_per_s={growth!r} "
                      f"client_late_ms_p50={float(np.median(sent))!r} "
                      f"client_late_ms_max={float(np.max(sent))!r}"],
        }

    # -- per-layer counters -----------------------------------------------------
    def counters(self) -> dict:
        """The window's share of the server's report: launches, requests,
        launch-weighted occupancy and request-weighted queue wait."""
        launches = requests = occ = wait = 0.0
        for key, (b, r, o, w) in self.after.items():
            b0, r0, o0, w0 = self.before.get(key, (0, 0, 0.0, 0.0))
            launches += b - b0
            requests += r - r0
            occ += o - o0
            wait += w - w0
        return {"launches": launches, "requests": requests,
                "occupancy_sum": occ, "wait_sum_s": wait}

    def release(self) -> None:
        self.srv.close()
        del self.srv

    # -- the comparison with the reference ----------------------------------------
    def check(self) -> list:
        rng = np.random.default_rng([self.seed, 7])
        keys = sorted(self.served)
        bad = 0
        if keys:
            cyc = [self.served[k].cycles for k in keys]
            for j in common.sample(rng, len(keys), self.t["check_requests"],
                                   must=int(np.argmax(cyc))):
                got = self.served[keys[j]]
                built = self.traffic.programs[self.traffic.requests[keys[j]]]
                want = common.expect(built, self.cfg, self.cfg["scheduler"],
                                     self.cfg["n_fu"])
                bad += common.differs(got.cycles, got.halted,
                                      got.schedule_tuple(), want)
        return [common.Check("requests_unanswered", self.unanswered, 0),
                common.Check("requests_differing_from_reference", bad, 0)]
