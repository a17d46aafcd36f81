"""hts.serve — continuous batching over the population machine.

:func:`api.run_many` answers "simulate this population"; this module
answers "keep simulating whatever arrives".  A :class:`Server` accepts
single scenarios (``submit() -> Future[Result]``), routes each into an
open batch for its *shape bucket*, and launches buckets through the same
compiled population machine everything else uses — so a serving workload
gets batched-throughput economics on an open arrival stream instead of a
pre-packed population.

The pieces, and why each exists:

* **Bucket router** — the compiled machine is shaped by the program-table
  size and the frontend-stream width, so requests are keyed by
  ``(prog_bucket(p_len), prog_bucket(n_streams))`` (the same power-of-two
  ladder as :func:`batch.prog_bucket`).  One open batch per bucket.  The
  key is read straight off the :class:`~repro.core.hts.batch.Prepared`
  request (program length = code rows, stream count = stream-set size) —
  admission is the engine's hot path and never decodes the program.
* **Launch-on-full / launch-on-deadline** — a batch launches the moment
  it reaches ``max_batch`` (inline, inside ``submit``), or when its
  oldest request has waited ``deadline`` seconds (checked by ``poll()``,
  which ``submit`` also runs on entry).  The clock is injectable
  (:class:`ManualClock`) so deadline behaviour is deterministically
  testable.
* **Slice-and-refill compaction** (``slice_steps=``) — a static launch
  holds all its lanes until the *slowest* one halts, which is exactly
  where heterogeneous streams lose their batching win.  With
  ``slice_steps`` set, a launch instead runs the resumable machine
  (:func:`machine.make_machine` ``resumable=True``) in bounded slices:
  after each slice, lanes whose machines have halted are harvested
  (their futures resolve immediately) and the freed slots are
  **refilled** from the bucket's queue — the batch never idles a lane
  while requests wait.  The budget counts *machine steps* (while-loop
  trips), not cycles: under event-skip a step's cycle advance is data-
  dependent, and steps are where wall time actually goes, so only a step
  budget stops one event-dense request from stalling the whole width for
  an unbounded stretch.  ``slice_steps="auto"`` sizes each slice from
  the bucket's measured completed-request step counts, so a slice is a
  few typical requests long.  In this mode ``submit`` lets a bucket's
  queue deepen past ``max_batch`` (the queue *is* the refill reservoir)
  and launches on deadline, ``drain()``, or queue pressure.
* **Stable launch shapes** — partial batches are padded to the bucket's
  one lane width (``max_batch``, rounded up to a device multiple) by
  replicating the batch's first request, and ``pack_population(
  max_prog=bucket, max_streams=bucket)`` pins the other two shape axes,
  so *every* launch of a bucket presents the identical signature to the
  jitted runner: one XLA compile per bucket, ever (two for a sliced
  bucket: carry init + slice, both compiled once — the slice budget is
  traced, so adapting it never recompiles).  :meth:`Server.cache_info`
  proves it — ``jit_compiles`` reads the runners' own compilation-cache
  sizes (not a guess), so a warmed server asserts zero recompilation
  across arbitrarily many batches *and refills*.
* **Backpressure** — at most ``max_queue`` requests may be pending across
  all open batches; ``submit`` raises :class:`QueueFullError` beyond
  that, after first flushing any deadline-expired batches.  The one
  exception: a request that *completes* its bucket's batch is always
  admitted — it launches inline and frees ``max_batch`` slots, so
  refusing it would be an off-by-one that deadlocks an exactly-full
  queue.
* **Sharding** — ``ServeSpec(devices=N)`` routes every launch through the
  ``shard_map`` path (:mod:`shard` via ``run_many(devices=N)`` or the
  sharded resumable machine), so a multi-device host drains each batch
  across its devices; lane refill composes (the lane width is pinned to a
  device multiple once per server).
* **Service metrics** — every request records when it was submitted,
  put in a lane and resolved, and every launch why it started and how
  long it spent in its host phases (``hts.serve.*`` spans, which a
  ``jax.profiler`` trace also shows); :meth:`Server.report` gives
  per-bucket and per-tenant means, the tails of queue wait and time in a
  lane, and launch delay, wall and host share, over everything or from a
  given time on.

    >>> from repro.core import hts
    >>> with hts.serve(max_batch=4, deadline=0.01) as srv:
    ...     futs = [srv.submit(p) for p in programs]
    ...     srv.drain()
    ...     cycles = [f.result().cycles for f in futs]

The engine is deliberately single-threaded: launches happen inside
``submit``/``poll``/``drain`` on the caller's thread, and futures are
resolved before those calls return.  That keeps the semantics exactly
reproducible (no scheduler races) while preserving the asynchronous
*interface* — callers hold ``Future`` handles and may submit from
producer code that never looks at results.

Lifecycle: after :meth:`Server.close` (which flushes), ``submit``,
``poll`` and ``drain`` all raise ``RuntimeError`` — a closed server is
closed, not silently inert.  Leaving the ``with`` block normally closes
(flushes); leaving it on an exception calls :meth:`Server.abort`, which
*cancels* still-queued futures instead of launching work the caller will
never observe.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import Future
from typing import Optional, Sequence, Union

import numpy as np

from . import api, batch, machine
from .costs import SchedulerCosts
from .golden import HtsParams
from .policy import SchedPolicy
from .spans import Spans


class QueueFullError(RuntimeError):
    """``submit`` refused: ``max_queue`` requests already pending (and the
    incoming request would not have completed a batch)."""


# ---------------------------------------------------------------------------
# clocks (injectable for deterministic deadline tests)
# ---------------------------------------------------------------------------
class SystemClock:
    """Wall time (``time.monotonic``) — the production clock."""

    def now(self) -> float:
        return time.monotonic()


class ManualClock:
    """A clock that only moves when told to — deadline tests advance it
    explicitly, so launch-on-deadline is exact instead of sleep-flaky."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def now(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += float(dt)
        return self.t


# ---------------------------------------------------------------------------
# spec + reports
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """Everything a :class:`Server` is configured by.

    ``n_fu``/``scheduler``/``params``/``policy``/``event_skip``/
    ``max_cycles`` mean what they mean on :func:`api.run_many` and are
    shared by every request (they are compilation-relevant, so per-request
    variation would defeat the bucket cache; per-request *policies* still
    work — attach them to the program, e.g. ``Program.merge(priorities=
    ...)``, and leave ``policy=None``).

    ``max_batch`` — lanes per launch (every launch is padded to exactly
    this — rounded up to a device multiple — so it is also the bucket's
    compiled batch shape).
    ``max_queue`` — pending-request bound across all open batches.
    ``deadline`` — seconds an open batch may age before ``poll()``
    launches it partial.  ``devices`` — shard each launch over N devices
    (``None`` = single-device path).

    ``slice_steps`` — ``None`` (default) launches static batches that
    run to completion; an int runs every launch in slices of at most that
    many machine steps per lane, with halted lanes harvested and refilled
    from the bucket queue between slices (continuous batching); ``"auto"``
    sizes slices from the bucket's measured completed-request step counts
    (4x the median of the last 64, floor {AUTO_MIN}; first launch at
    {AUTO} steps).

    ``step_impl`` — the step-body lowering
    (:data:`~repro.core.hts.machine.STEP_IMPLS`) every launch runs
    under; compilation-relevant like the rest, so it is part of the
    bucket cache key via the machine spec.
    """
    scheduler: Union[str, SchedulerCosts] = "hts_spec"
    n_fu: Union[int, Sequence[int]] = 2
    params: HtsParams = HtsParams()
    policy: Optional[SchedPolicy] = None
    event_skip: bool = True
    max_cycles: int = 5_000_000
    max_batch: int = 8
    max_queue: int = 64
    deadline: float = 0.050
    devices: Optional[int] = None
    max_fu_per_class: Optional[int] = None
    slice_steps: Optional[Union[int, str]] = None
    step_impl: str = "xla"


#: first-launch slice budget (machine steps) under ``slice_steps="auto"``
#: (no measured completions yet to take a median of)
AUTO_SLICE_STEPS = 256
#: smallest auto slice — below this, per-slice dispatch overhead dominates
AUTO_SLICE_STEPS_MIN = 32
ServeSpec.__doc__ = ServeSpec.__doc__.format(AUTO=AUTO_SLICE_STEPS,
                                             AUTO_MIN=AUTO_SLICE_STEPS_MIN)


@dataclasses.dataclass(frozen=True)
class CacheInfo:
    """Compilation accounting.  ``hits``/``misses`` count bucket-runner
    lookups at launch time (miss = first launch of a bucket); ``entries``
    is the number of distinct buckets launched; ``jit_compiles`` is the
    *runners' own* compilation-cache population — the honest number, read
    from the jitted callables (a sliced bucket's runner is two callables:
    carry init + slice), not inferred.  A warmed server launches batch
    after batch — and refill after refill — with ``jit_compiles``
    frozen."""
    hits: int
    misses: int
    entries: int
    jit_compiles: int


@dataclasses.dataclass(frozen=True)
class BucketStats:
    batches: int
    requests: int
    pad_lanes: int
    occupancy: float            # mean real-lane fraction (measured per
    #                             slice for compacted launches)
    mean_wait: float            # seconds queued before a lane ran it


@dataclasses.dataclass(frozen=True)
class TenantStats:
    requests: int
    mean_wait: float


#: why a launch started: its oldest request aged past ``deadline``
#: (``poll``), a flush (``drain``/``close``), a full queue under
#: slice-and-refill, or a full batch in static mode (both in ``submit``)
LAUNCH_REASONS = ("deadline", "drain", "pressure", "full")
#: the host phases of a launch, each a ``hts.serve.<phase>`` span
LAUNCH_PHASES = ("pack", "slice", "harvest", "refill")


@dataclasses.dataclass(frozen=True)
class ServeReport:
    """Service metrics over the requests submitted and the launches
    started in the report's span of time (all of them by default).

    Times are seconds on the server's clock.  A request is submitted
    when it is enqueued; ``submit_hold_*`` is how long ``submit()`` held
    the caller before that (the deadline flush it runs first may
    launch).  ``queue_wait_*`` runs from submit to the request's first
    slice (as ``BucketStats.mean_wait``), ``in_lane_*`` from there to its
    result, and ``slices_*`` counts the slices it spent in a lane.
    ``launch_delay_*`` runs from a launch's oldest request's submit to the
    launch's start; ``launch_wall_*`` is how long a launch held the
    caller's thread, of which ``host_share`` (over all launches) was not
    spent in ``slice`` (dispatch and the wait on the device).
    ``launch_queue_*`` counts the requests pending server-wide when a
    launch started, ``launch_slices_*`` the slices it ran.  ``phase_s``
    totals each of :data:`LAUNCH_PHASES`.  A statistic with nothing to
    describe is NaN."""
    requests: int
    batches: int
    per_bucket: dict
    per_tenant: dict
    submit_hold_mean: float
    submit_hold_max: float
    queue_wait_p50: float
    queue_wait_p95: float
    in_lane_mean: float
    in_lane_p50: float
    in_lane_p95: float
    slices_p50: float
    slices_p95: float
    launch_delay_mean: float
    launch_delay_max: float
    launch_wall_mean: float
    launch_wall_max: float
    launch_queue_mean: float
    launch_queue_max: float
    launch_slices_mean: float
    launch_slices_max: float
    host_share: float
    phase_s: dict
    launches_by_reason: dict

    def table(self) -> str:
        lines = [f"served {self.requests} requests in {self.batches} "
                 f"batches",
                 f"{'bucket':<14} {'batches':>7} {'reqs':>6} {'occ':>6} "
                 f"{'wait(ms)':>9}"]
        for key, b in sorted(self.per_bucket.items()):
            lines.append(f"{str(key):<14} {b.batches:>7} {b.requests:>6} "
                         f"{b.occupancy:>6.2f} {b.mean_wait * 1e3:>9.3f}")
        if self.per_tenant:
            lines.append(f"{'tenant':<14} {'':>7} {'reqs':>6} {'':>6} "
                         f"{'wait(ms)':>9}")
            for name, t in sorted(self.per_tenant.items()):
                lines.append(f"{name:<14} {'':>7} {t.requests:>6} {'':>6} "
                             f"{t.mean_wait * 1e3:>9.3f}")

        for label, a, b in (
                ("submit hold mean/max", self.submit_hold_mean,
                 self.submit_hold_max),
                ("queue wait p50/p95", self.queue_wait_p50,
                 self.queue_wait_p95),
                ("in lane p50/p95", self.in_lane_p50, self.in_lane_p95),
                ("launch delay mean/max", self.launch_delay_mean,
                 self.launch_delay_max),
                ("launch wall mean/max", self.launch_wall_mean,
                 self.launch_wall_max)):
            lines.append(f"{label + ' (ms)':<27} {a * 1e3:>9.3f} "
                         f"{b * 1e3:>9.3f}")
        for label, a, b in (
                ("slices a request p50/p95", self.slices_p50,
                 self.slices_p95),
                ("queue at launch mean/max", self.launch_queue_mean,
                 self.launch_queue_max),
                ("slices a launch mean/max", self.launch_slices_mean,
                 self.launch_slices_max)):
            lines.append(f"{label:<27} {a:>9.1f} {b:>9.1f}")
        lines.append(f"{'host share of launch wall':<27} "
                     f"{self.host_share:>9.3f}")
        lines.append(f"{'launches by reason':<27} " + " ".join(
            f"{k}={n}" for k, n in self.launches_by_reason.items()))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
_TREE_OPS = None


def _tree_ops():
    """Two shared jitted helpers for sliced launches: gather W rows out of
    a device-resident tree (``take``), and scatter W replacement rows into
    one (``put``).  Index vectors are padded to the fixed lane width with
    duplicates that carry identical rows (so scatter order cannot matter),
    which keeps each helper at one compilation per tree shape.  Per-lane
    eager indexing would instead pay dispatch overhead per *field* per
    lane — on a CPU host that overhead dwarfs the slice compute itself."""
    global _TREE_OPS
    if _TREE_OPS is None:
        import jax

        take = jax.jit(lambda tree, idx: jax.tree_util.tree_map(
            lambda v: v[idx], tree))
        put = jax.jit(lambda tree, idx, rows: jax.tree_util.tree_map(
            lambda v, r: v.at[idx].set(r), tree, rows))
        _TREE_OPS = (take, put)
    return _TREE_OPS


@dataclasses.dataclass
class _Request:
    prep: batch.Prepared
    tenant: str
    t_enter: float              # submit() was called
    t_submit: float             # enqueued, after submit()'s deadline flush
    future: Future


@dataclasses.dataclass
class _OpenBatch:
    t_open: float
    requests: list


@dataclasses.dataclass(frozen=True)
class _RequestRow:
    """One resolved request, on the server's clock."""
    bucket: tuple
    tenant: str
    enter: float                # submit() was called
    submit: float               # enqueued
    fill: float                 # its first slice started
    done: float                 # its future resolved
    slices: int                 # slices it spent in a lane


@dataclasses.dataclass(frozen=True)
class _LaunchRow:
    """One finished launch, on the server's clock."""
    bucket: tuple
    start: float
    end: float
    reason: str                 # one of LAUNCH_REASONS
    queue: int                  # requests pending server-wide at start
    oldest: float               # submit time of its oldest request
    pad: int
    occupancy: float
    slices: int
    phase_s: dict               # LAUNCH_PHASES -> seconds


def _pct(xs, q: float) -> float:
    return float(np.percentile(xs, q)) if len(xs) else float("nan")


def _mean(xs) -> float:
    return float(np.mean(xs)) if len(xs) else float("nan")


def _max(xs) -> float:
    return float(np.max(xs)) if len(xs) else float("nan")


class Server:
    """The continuous-batching engine.  Build via :func:`serve`."""

    def __init__(self, spec: ServeSpec = ServeSpec(), *, clock=None):
        self.spec = spec
        self._clock = clock if clock is not None else SystemClock()
        self._cost = api._norm_costs(spec.scheduler)
        widest = max(batch.norm_n_fu(spec.n_fu))
        self._max_fu = (spec.max_fu_per_class
                        if spec.max_fu_per_class is not None
                        else max(4, widest))
        if widest > self._max_fu:
            raise ValueError(f"n_fu {widest} exceeds max_fu_per_class "
                             f"{self._max_fu}")
        if spec.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if spec.max_queue < spec.max_batch:
            raise ValueError("max_queue must be >= max_batch")
        sc = spec.slice_steps
        if sc is not None and sc != "auto" and (
                not isinstance(sc, (int, np.integer)) or sc < 1):
            raise ValueError('slice_steps must be None, "auto", or a '
                             f'positive int, got {sc!r}')
        self._compaction = sc is not None
        # lane width: max_batch rounded up to a device multiple, so the
        # sharded paths see one fixed, divisible shape per bucket
        mult = spec.devices or 1
        self._lanes = -(-spec.max_batch // mult) * mult
        self._open: dict[tuple[int, int], _OpenBatch] = {}
        self._runners: dict[tuple[int, int], object] = {}
        self._hits = 0
        self._misses = 0
        self._pending = 0
        self._closed = False
        self._req_rows: list = []      # _RequestRow per resolved request
        self._batch_rows: list = []    # _LaunchRow per finished launch
        self._done_steps: dict[tuple[int, int], list] = {}

    # -- admission ----------------------------------------------------------
    def bucket_of(self, program) -> tuple[int, int]:
        """The shape-bucket key a program routes to:
        ``(prog_bucket(p_len), prog_bucket(n_streams, floor=1))``."""
        return self._bucket_key(batch.prepare(program))

    @staticmethod
    def _bucket_key(prep: batch.Prepared) -> tuple[int, int]:
        # the hot admission path: length is the code-row count and the
        # stream count is the stream-set size — no program decode here
        n_streams = len(prep.streams) if prep.streams is not None else 1
        return (batch.prog_bucket(len(prep.code)),
                batch.prog_bucket(n_streams, floor=1))

    def _require_open(self) -> None:
        if self._closed:
            raise RuntimeError("server is closed")

    def submit(self, program, *, tenant: str = "-") -> Future:
        """Enqueue one scenario; the Future resolves to its
        :class:`~repro.core.hts.api.Result` when a launch runs it
        (inline on fill or queue pressure, or on a later
        ``poll``/``drain``).

        Raises :class:`QueueFullError` when ``max_queue`` requests are
        already pending (after flushing any deadline-expired batches) —
        unless this request completes its bucket's batch, in which case
        it is admitted and the batch launches inline, freeing its slots.
        Open-loop producers must shed or retry on refusal.
        """
        self._require_open()
        t_enter = self._clock.now()
        self.poll()                     # free space deadlines already owe
        prep = batch.prepare(program)
        key = self._bucket_key(prep)
        ob = self._open.get(key)
        waiting = len(ob.requests) if ob is not None else 0
        full = self._pending >= self.spec.max_queue
        if full and waiting + 1 < self.spec.max_batch:
            raise QueueFullError(
                f"{self._pending} requests pending >= max_queue "
                f"{self.spec.max_queue}")
        req = _Request(prep=prep, tenant=tenant, t_enter=t_enter,
                       t_submit=self._clock.now(), future=Future())
        if ob is None:
            ob = self._open[key] = _OpenBatch(t_open=req.t_submit,
                                              requests=[])
        ob.requests.append(req)
        self._pending += 1
        # static mode launches the moment a batch fills; compaction mode
        # lets the bucket queue deepen (it is the refill reservoir) and
        # launches on deadline/drain — or right here under queue pressure
        if len(ob.requests) >= self.spec.max_batch and (
                full or not self._compaction):
            self._launch(key, "pressure" if self._compaction else "full")
        return req.future

    def poll(self) -> int:
        """Launch every open batch whose oldest request has aged past
        ``deadline``.  Returns the number of batches launched."""
        self._require_open()
        now = self._clock.now()
        due = [k for k, ob in self._open.items()
               if now - ob.t_open >= self.spec.deadline]
        for k in due:
            self._launch(k, "deadline")
        return len(due)

    def drain(self) -> int:
        """Launch every open batch regardless of age (flush)."""
        self._require_open()
        keys = list(self._open)
        for k in keys:
            self._launch(k, "drain")
        return len(keys)

    def close(self) -> None:
        """Flush, then refuse further ``submit``/``poll``/``drain``.
        Idempotent."""
        if self._closed:
            return
        self.drain()
        self._closed = True

    def abort(self) -> None:
        """Discard queued work without launching: cancel every pending
        future, empty the queue, close the server.  This is the
        exception-path exit (``with`` blocks call it when unwinding) —
        flushing there would burn simulation time on results nobody will
        ever read."""
        if self._closed:
            return
        for ob in self._open.values():
            for r in ob.requests:
                r.future.cancel()
        self._open.clear()
        self._pending = 0
        self._closed = True

    def __enter__(self) -> "Server":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.abort()
        else:
            self.close()

    @property
    def pending(self) -> int:
        """Requests admitted whose futures have not yet resolved."""
        return self._pending

    # -- execution ----------------------------------------------------------
    def _machine_spec(self) -> machine.MachineSpec:
        # mirror run_many exactly (policy/cost-stripped params) so the runner
        # fetched here for accounting IS the runner run_many executes
        return machine.MachineSpec(
            params=dataclasses.replace(self.spec.params,
                                       policy=SchedPolicy(), fu_cost=None),
            costs=self._cost, event_skip=self.spec.event_skip,
            max_cycles=self.spec.max_cycles,
            max_fu_per_class=self._max_fu,
            step_impl=self.spec.step_impl)

    def _runner(self, key: tuple[int, int]):
        r = self._runners.get(key)
        if r is not None:
            self._hits += 1
            return r
        spec = self._machine_spec()
        r = (api._slicer_for(spec, key[0], self.spec.devices)
             if self._compaction
             else api._runner_for(spec, key[0], self.spec.devices))
        self._runners[key] = r
        self._misses += 1
        return r

    def _pack(self, preps, key: tuple[int, int]) -> batch.PackedPopulation:
        return batch.pack_population(
            preps, params=self.spec.params, n_fu=self.spec.n_fu,
            policy=self.spec.policy, max_prog=key[0], max_streams=key[1])

    def _resolve(self, key, req: _Request, result, t_filled: float,
                 slices: int) -> None:
        """Resolve one request's future (and its accounting) — the ONLY
        place ``_pending`` decrements, so a request admitted is a request
        either resolved or still counted."""
        self._pending -= 1
        self._req_rows.append(_RequestRow(
            bucket=key, tenant=req.tenant, enter=req.t_enter,
            submit=req.t_submit, fill=t_filled, done=self._clock.now(),
            slices=slices))
        if result is not None:
            self._done_steps.setdefault(key, []).append(
                int(np.asarray(result.raw["steps"])))
            req.future.set_result(result)
        else:
            req.future.set_exception(api.SimulationError(
                f"request {req.prep.name!r} (tenant {req.tenant!r}) did "
                f"not halt within {self.spec.max_cycles} cycles"))

    def _launch(self, key: tuple[int, int], reason: str) -> None:
        ob = self._open.pop(key, None)
        if ob is None or not ob.requests:
            return
        spans = Spans(self._clock.now)
        queue = self._pending
        try:
            with spans("hts.serve.launch", bucket=str(key), lanes=self._lanes,
                       queue=queue, reason=reason):
                run = (self._launch_sliced if self._compaction
                       else self._launch_static)
                pad, occupancy, slices = run(key, ob.requests, spans)
        except BaseException as e:
            # exception-safe: a failed launch fails its *own* futures and
            # restores the queue accounting, instead of leaking hung
            # futures and permanently shrinking capacity
            for r in ob.requests:
                if not r.future.done():
                    self._pending -= 1
                    r.future.set_exception(e)
            raise
        _, start, end = spans.rows[-1]       # the launch span closes last
        self._batch_rows.append(_LaunchRow(
            bucket=key, start=start, end=end, reason=reason, queue=queue,
            oldest=ob.requests[0].t_submit, pad=pad, occupancy=occupancy,
            slices=slices,
            phase_s={p: spans.seconds(f"hts.serve.{p}")
                     for p in LAUNCH_PHASES}))

    def _launch_static(self, key: tuple[int, int], reqs: list,
                       spans: Spans) -> tuple:
        """One run-to-completion launch; returns (pad lanes, occupancy,
        slices) for the launch's row."""
        self._runner(key)           # cache accounting (run_many reuses it)
        # pad to the bucket's one-and-only launch shape (replicating the
        # first request — pad results are discarded)
        pad = self._lanes - len(reqs)
        preps = [r.prep for r in reqs] + [reqs[0].prep] * pad
        with spans("hts.serve.pack"):
            pop = self._pack(preps, key)
        t_launch = self._clock.now()
        with spans("hts.serve.slice"):
            res = api.run_many(pop, scheduler=self._cost,
                               event_skip=self.spec.event_skip,
                               max_cycles=self.spec.max_cycles,
                               max_fu_per_class=self._max_fu,
                               devices=self.spec.devices, check=False,
                               step_impl=self.spec.step_impl)
        with spans("hts.serve.harvest"):
            for i, r in enumerate(reqs):
                self._resolve(key, r,
                              res[i] if bool(res.halted[i]) else None,
                              t_launch, 1)
        return pad, len(reqs) / self._lanes, 1

    # -- slice-and-refill (compaction) --------------------------------------
    def _slice_budget(self, key: tuple[int, int]) -> int:
        sc = self.spec.slice_steps
        if sc == "auto":
            hist = self._done_steps.get(key)
            if not hist:
                return AUTO_SLICE_STEPS
            # a few typical requests per slice: fine enough that an
            # event-dense straggler cannot stall the width for long,
            # coarse enough that dispatch overhead stays amortised
            return max(AUTO_SLICE_STEPS_MIN,
                       4 * int(np.median(hist[-64:])))
        return int(sc)

    def _lane_result(self, req: _Request, out: dict, n_fu_row,
                     wall_us: float) -> api.Result:
        fu = tuple(int(x) for x in n_fu_row)
        pol = batch.norm_policy(self.spec.policy, req.prep,
                                self.spec.params)
        return api._machine_result(req.prep.name, self._cost.name, fu, out,
                                   wall_us, pol, self._max_fu,
                                   req.prep.streams)

    def _refill_rows(self, key, fresh, req: _Request):
        """Host-side rows that splice a fresh lane for ``req`` into a
        running launch: the packed row for all 11 machine arguments, and a
        carry row that is the fresh-state template with the two program-
        dependent fields (``pc``, ``mem``) overwritten — the exact state
        ``init`` would have built for it."""
        row = self._pack([req.prep], key)
        arow = [b[0] for b in row.machine_args()]
        crow = dict(fresh)
        crow["pc"] = row.streams[0][:, 0]
        crow["mem"] = row.mem[0]
        return crow, arow

    def _launch_sliced(self, key: tuple[int, int], reqs: list,
                       spans: Spans) -> tuple:
        """Run one bucket's queue through ``self._lanes`` lanes with
        bounded step slices, harvesting halted lanes and refilling their
        slots between slices, until the queue is dry and every lane has
        drained.  Each request's future resolves the moment its own lane
        halts — not when the batch does.  Returns (pad lanes, occupancy,
        slices) for the launch's row.

        The carry and the 11 machine arguments stay **device-resident**
        across slices: per slice only the three per-lane liveness fields
        come back to the host (to decide harvests), then *all* dead lanes
        are gathered in one jitted tree-take and *all* refills spliced in
        one jitted tree-put (:func:`_tree_ops`).  The state itself never
        round-trips, so the per-slice host cost is independent of
        ``HtsParams`` capacities and of how many lanes turned over."""
        import jax
        import jax.numpy as jnp

        take_rows, put_rows = _tree_ops()
        rm = self._runner(key)
        queue = list(reqs)                       # FIFO submit order
        W = self._lanes
        take, queue = queue[:W], queue[W:]
        pad = W - len(take)
        with spans("hts.serve.pack"):
            pop = self._pack([r.prep for r in take] + [take[0].prep] * pad,
                             key)
            args = [jnp.asarray(a) for a in pop.machine_args()]
            n_fu_host = np.array(pop.machine_args()[2])  # host mirror
            carry = dict(rm.init(*args))
            # one fresh state row as the refill template: machine.init
            # only varies pc and mem with the program (documented
            # invariant), so a fresh row for ANY program is this
            # template + those two fields
            fresh = jax.device_get({k: v[0] for k, v in carry.items()})
            # retire pad lanes before the first slice: marking the clones
            # halted makes them step fixed points and immediately
            # refillable
            if pad:
                carry["halted"] = carry["halted"].at[len(take):].set(True)
        lanes: list = list(take) + [None] * pad
        t_fill = [self._clock.now()] * W
        n_slices = [0] * W                       # slices run per lane
        served = 0
        occ_num = 0.0
        occ_den = 0
        while any(r is not None for r in lanes):
            occ_num += sum(r is not None for r in lanes) / W
            occ_den += 1
            for i, r in enumerate(lanes):
                n_slices[i] += r is not None
            budget = np.int32(self._slice_budget(key))
            with spans("hts.serve.slice"):
                carry = dict(rm.run_slice(carry, *args, budget))
                now = self._clock.now()
                halted, overflow, cycle = jax.device_get(
                    (carry["halted"], carry["overflow"], carry["cycle"]))
            dead = halted | overflow | (cycle >= self.spec.max_cycles)
            done = [i for i in range(W)
                    if lanes[i] is not None and dead[i]]
            if not done:
                continue
            with spans("hts.serve.harvest"):
                # one gather for every lane that died this slice (index
                # vector padded to W so the helper keeps a single compiled
                # shape)
                idx = np.asarray(done + [done[0]] * (W - len(done)),
                                 np.int32)
                rows = jax.device_get(take_rows(carry, idx))
                for j, i in enumerate(done):
                    r = lanes[i]
                    row = rm.collect({k: v[j] for k, v in rows.items()})
                    res = self._lane_result(r, row, n_fu_host[i],
                                            (now - t_fill[i]) * 1e6)
                    self._resolve(key, r, res if res.halted else None,
                                  t_fill[i], n_slices[i])
                    served += 1
                    lanes[i] = None
            refill = done[:len(queue)]
            if not refill:
                continue
            with spans("hts.serve.refill"):
                ref_crows: list[dict] = []
                ref_arows: list[list] = []
                for i in refill:
                    nxt = queue.pop(0)
                    crow, arow = self._refill_rows(key, fresh, nxt)
                    ref_crows.append(crow)
                    ref_arows.append(arow)
                    n_fu_host[i] = np.array(arow[2])
                    lanes[i] = nxt
                    t_fill[i] = now
                    n_slices[i] = 0
                # one splice for every refill this slice (padded with
                # duplicates of refill 0 — identical rows, so the scatter
                # is order-independent)
                k = W - len(refill)
                ridx = np.asarray(refill + [refill[0]] * k, np.int32)
                ref_crows += [ref_crows[0]] * k
                ref_arows += [ref_arows[0]] * k
                crows = {f: np.stack([c[f] for c in ref_crows])
                         for f in ref_crows[0]}
                arows = [np.stack([a[j] for a in ref_arows])
                         for j in range(len(args))]
                carry, args = put_rows((carry, args), ridx, (crows, arows))
                carry, args = dict(carry), list(args)
        return max(0, W - served), occ_num / max(occ_den, 1), occ_den

    # -- introspection ------------------------------------------------------
    def cache_info(self) -> CacheInfo:
        parts = []
        for r in self._runners.values():
            if isinstance(r, machine.ResumableMachine):
                parts += [r.init, r.run_slice]
            else:
                parts.append(r)
        distinct = {id(p): p for p in parts}
        compiles = 0
        for p in distinct.values():
            size = getattr(p, "_cache_size", None)
            compiles += int(size()) if callable(size) else 0
        return CacheInfo(hits=self._hits, misses=self._misses,
                         entries=len(self._runners), jit_compiles=compiles)

    def report(self, since: Optional[float] = None) -> ServeReport:
        """Service metrics (:class:`ServeReport`) over the requests
        submitted and the launches started at or after ``since`` (a time
        on the server's clock; everything by default)."""
        lo = -np.inf if since is None else since
        reqs = [r for r in self._req_rows if r.submit >= lo]
        launches = [b for b in self._batch_rows if b.start >= lo]
        per_bucket: dict = {}
        for key in {b.bucket for b in launches}:
            rows = [r for r in reqs if r.bucket == key]
            mine = [b for b in launches if b.bucket == key]
            per_bucket[key] = BucketStats(
                batches=len(mine),
                requests=len(rows),
                pad_lanes=sum(b.pad for b in mine),
                occupancy=float(np.mean([b.occupancy for b in mine])),
                mean_wait=_mean([r.fill - r.submit for r in rows]))
        per_tenant: dict = {}
        for tenant in {r.tenant for r in reqs}:
            rows = [r for r in reqs if r.tenant == tenant]
            per_tenant[tenant] = TenantStats(
                requests=len(rows),
                mean_wait=_mean([r.fill - r.submit for r in rows]))
        hold = [r.submit - r.enter for r in reqs]
        wait = [r.fill - r.submit for r in reqs]
        in_lane = [r.done - r.fill for r in reqs]
        slices = [r.slices for r in reqs]
        delay = [b.start - b.oldest for b in launches]
        wall = [b.end - b.start for b in launches]
        queue = [b.queue for b in launches]
        runs = [b.slices for b in launches]
        phase_s = {p: sum(b.phase_s[p] for b in launches)
                   for p in LAUNCH_PHASES}
        return ServeReport(
            requests=len(reqs), batches=len(launches),
            per_bucket=per_bucket, per_tenant=per_tenant,
            submit_hold_mean=_mean(hold), submit_hold_max=_max(hold),
            queue_wait_p50=_pct(wait, 50), queue_wait_p95=_pct(wait, 95),
            in_lane_mean=_mean(in_lane), in_lane_p50=_pct(in_lane, 50),
            in_lane_p95=_pct(in_lane, 95),
            slices_p50=_pct(slices, 50), slices_p95=_pct(slices, 95),
            launch_delay_mean=_mean(delay), launch_delay_max=_max(delay),
            launch_wall_mean=_mean(wall), launch_wall_max=_max(wall),
            launch_queue_mean=_mean(queue), launch_queue_max=_max(queue),
            launch_slices_mean=_mean(runs), launch_slices_max=_max(runs),
            host_share=(1.0 - phase_s["slice"] / sum(wall) if sum(wall)
                        else float("nan")),
            phase_s=phase_s,
            launches_by_reason={
                why: sum(b.reason == why for b in launches)
                for why in LAUNCH_REASONS})


def serve(spec: Optional[ServeSpec] = None, *, clock=None,
          **overrides) -> Server:
    """Build a :class:`Server` — ``hts.serve()`` is the front door.

    Pass a :class:`ServeSpec`, keyword overrides for its fields, or both
    (overrides win).  ``clock`` injects a time source
    (:class:`ManualClock` in tests; wall time otherwise).  Usable as a
    context manager: ``with hts.serve(...) as srv: ...`` flushes and
    closes on normal exit, aborts (cancels queued futures) on an
    exception.
    """
    if spec is None:
        spec = ServeSpec()
    if overrides:
        spec = dataclasses.replace(spec, **overrides)
    return Server(spec, clock=clock)


__all__ = ["serve", "Server", "ServeSpec", "ServeReport", "BucketStats",
           "TenantStats", "CacheInfo", "QueueFullError", "SystemClock",
           "ManualClock", "AUTO_SLICE_STEPS", "LAUNCH_REASONS",
           "LAUNCH_PHASES"]
