"""Unified HTS simulation facade: ``hts.run``, ``hts.run_many``, ``hts.sweep``.

One entry point for every caller of the reproduction — benchmarks, examples
and tests no longer thread ``assembler.assemble → machine.simulate(...)`` /
``golden.run(...)`` by hand (each with a different signature):

    >>> from repro.core import hts
    >>> p = hts.Program("demo")
    >>> x = p.input(0x10, 4)
    >>> fft = p.task("fft_256", in_=x, out=4)
    >>> r = hts.run(p, scheduler="hts_spec", n_fu=2)
    >>> r.cycles, r.utilization, r.schedule[0].func_name
    >>> r.speedup_vs(hts.run(p, scheduler="naive", n_fu=2))

``run`` accepts a :class:`~repro.core.hts.builder.Program`, a built program,
a ``Bench``, raw assembly text, or a (P, 4) machine-code array, and executes
it on either backend:

* ``backend="jax"``    — the compiled ``lax.while_loop`` machine
  (:mod:`machine`), event-skip by default;
* ``backend="golden"`` — the pure-Python cycle-accurate oracle
  (:mod:`golden`).

Both return the same :class:`Result` with identical per-task schedule rows
(the two simulators are schedule-equivalence-tested).

The axes model
--------------
Every argument of the compiled machine is a runtime input, so batching is
a choice of ``vmap`` axes over its 11-argument signature (the 9th/10th
are the heterogeneous FU cost table and the eft-arbiter flag, the 11th
the per-tenant frontend stream table, ``frontend.py``).  Three named
axes compose (``_vmapped`` stacks them outermost-first):

* the **scenario** axis — everything batched: a *population* of programs,
  each with its own images, FU counts, policy tables and stream tables.
  ``run_many``
  drives it and returns a :class:`PopulationResult`; ``batch.py`` packs
  programs of one shape bucket into the common-shape arrays.
* the **n_fu** axis — only the FU configuration batched (the Fig-10
  strong-scaling machinery).  ``sweep`` drives it; handed a population it
  composes scenario × n_fu in one call.
* the **policy** axis — only the ``prio``/``quota``/``rs_cap`` tables
  batched (weights are runtime data, so policy sweeps never recompile).

One compilation is cached per ``(MachineSpec, max_prog, axes)`` — i.e. per
static shape bucket — no matter how many scenarios, FU points or policies
ride through it.

``compare`` is the differential runner: golden oracle vs the compiled
machine with event-skip on *and* off, per scheduler, schedule-tuple
equality asserted — the workhorse behind the seeded multi-tenant fuzzer
(``workloads.py`` / tests/test_hts_multitenant.py).  Handed a sequence of
programs it verifies a whole population: one vmapped machine batch per
(scheduler, event-skip mode), checked scenario-by-scenario against a
golden loop.

Multi-tenant metrics live on :class:`Result`: ``by_pid()`` /
``schedule_for`` slice the schedule by owning process, ``app_makespan``
is one tenant's finish cycle, and ``fairness`` reports per-tenant
slowdown vs solo runs (max slowdown = the fairness figure of merit),
annotated with each pid's priority weight (``FairnessReport.by_weight``
is the slowdown-vs-priority curve).

QoS scheduling: ``run``/``sweep``/``compare`` all take a
``policy=``:class:`~repro.core.hts.policy.SchedPolicy` (per-pid priority
weights + per-class FU quotas for the RS arbiter).  Resolution order:
explicit argument > policy attached to the program (e.g. by
``Program.merge(priorities=...)``) > ``params.policy``.  Policies are
runtime data to the compiled machine — sweeping them never recompiles.

See docs/API.md for a runnable tour of this module.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional, Sequence, Union

import numpy as np
from jax.profiler import annotate_function

from . import batch, golden, machine
from .batch import PackedPopulation
from .machine import STEP_IMPLS
from .costs import (ALL_SCHEDULERS, FUNC_NAMES, NUM_FUNCS, SchedulerCosts,
                    costs_by_name, fu_cost_tuple, norm_fu_cost)
from .frontend import StreamSet
from .golden import HtsParams
from .policy import SchedPolicy
from .spans import Spans


class SimulationError(RuntimeError):
    """A simulation did not halt (hit ``max_cycles``) or overflowed."""


# program normalisation lives in batch.py (packing needs it too); the
# private names remain importable here for callers of the old layout.
_Prepared = batch.Prepared
_prepare = batch.prepare
_norm_n_fu = batch.norm_n_fu
_norm_policy = batch.norm_policy


def _norm_costs(scheduler) -> SchedulerCosts:
    return (costs_by_name(scheduler) if isinstance(scheduler, str)
            else scheduler)


def _is_population(program) -> bool:
    """A sequence of programs (or a packed batch) vs one program.

    Strings (assembly) and ndarrays (machine code) are single programs;
    lists/tuples of program-ish objects are populations.
    """
    return isinstance(program, (PackedPopulation, list, tuple))


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TaskRow:
    """One scheduled task: dispatch/issue/complete/broadcast cycles."""
    uid: int
    func: int
    dispatch: int
    issue: int
    complete: int
    broadcast: int
    aborted: bool
    pid: int = 0                 # owning process (multi-tenant accounting)

    @property
    def func_name(self) -> str:
        return FUNC_NAMES.get(self.func, f"acc_{self.func:x}")

    def astuple(self) -> tuple:
        return (self.uid, self.func, self.dispatch, self.issue,
                self.complete, self.broadcast, self.aborted, self.pid)


@dataclasses.dataclass(frozen=True)
class Result:
    """Uniform simulation outcome (either backend)."""
    program: str
    scheduler: str
    backend: str
    n_fu: tuple[int, ...]
    cycles: int
    halted: bool
    schedule: tuple[TaskRow, ...]
    spec_aborted: int
    stall_cycles: int
    fu_busy_cycles: tuple[int, ...]     # per existing unit, class-major order
    wall_us: float
    raw: Any = dataclasses.field(repr=False, compare=False, default=None)
    policy: Optional[SchedPolicy] = dataclasses.field(
        default=None, compare=False)    # arbitration policy this run used
    #: per-stream dispatch-stall cycles (one entry for merged-frontend runs)
    fe_stall: tuple[int, ...] = dataclasses.field(default=(), compare=False)
    #: the per-tenant frontends this run dispatched through (None = the
    #: historical one merged in-order stream)
    streams: Optional[StreamSet] = dataclasses.field(
        default=None, compare=False)

    @property
    def n_tasks(self) -> int:
        return len(self.schedule)

    @property
    def utilization(self) -> float:
        """Mean busy fraction across the accelerator units that exist."""
        units = sum(self.n_fu)
        if units == 0 or self.cycles == 0:
            return 0.0
        return float(sum(self.fu_busy_cycles)) / (units * self.cycles)

    def speedup_vs(self, other: "Result") -> float:
        """How much faster this run is than ``other`` (>1 ⇒ faster)."""
        return other.cycles / self.cycles

    def schedule_tuple(self) -> list[tuple]:
        """Canonical rows, comparable across backends."""
        return [row.astuple() for row in self.schedule]

    # ------------------------------------------------- multi-tenant metrics
    @property
    def pids(self) -> tuple[int, ...]:
        """Process ids present in the schedule, ascending."""
        return tuple(sorted({row.pid for row in self.schedule}))

    def by_pid(self) -> dict[int, tuple[TaskRow, ...]]:
        """Per-process schedule slices (each app's rows, uid order)."""
        out: dict[int, list[TaskRow]] = {}
        for row in self.schedule:
            out.setdefault(row.pid, []).append(row)
        return {pid: tuple(rows) for pid, rows in sorted(out.items())}

    def schedule_for(self, pid: int) -> tuple[TaskRow, ...]:
        """The schedule rows owned by process ``pid``."""
        return tuple(row for row in self.schedule if row.pid == pid)

    def app_makespan(self, pid: int) -> int:
        """Completion cycle of ``pid``'s last non-aborted task (0 if none).

        The per-application makespan under sharing: how long *this tenant*
        waited, regardless of when the other tenants drained.
        """
        done = [row.complete for row in self.schedule
                if row.pid == pid and not row.aborted and row.complete >= 0]
        return max(done, default=0)

    def fairness(self, solo: "dict[int, Result]") -> "FairnessReport":
        """Slowdown of each tenant vs its solo run on the same pool.

        ``solo`` maps pid → the tenant's standalone :class:`Result`.
        Slowdown(pid) = shared app makespan / solo makespan (≥ ~1.0; large
        values mean the scheduler starves that tenant).  ``max_slowdown`` is
        the fairness figure of merit (Fusco et al. 2022 use the same metric
        for hardware-HEFT workloads).
        """
        slowdowns = {}
        for pid, solo_res in sorted(solo.items()):
            base = solo_res.app_makespan(pid) or solo_res.cycles
            shared = self.app_makespan(pid)
            slowdowns[pid] = shared / base if base else float("inf")
        pol = self.policy or SchedPolicy()
        return FairnessReport(
            slowdowns=slowdowns,
            max_slowdown=max(slowdowns.values(), default=0.0),
            mean_slowdown=(sum(slowdowns.values()) / len(slowdowns)
                           if slowdowns else 0.0),
            weights={pid: pol.weight_of(pid) for pid in slowdowns},
            frontend=({pid: self.frontend_metrics(pid) for pid in slowdowns}
                      if self.streams is not None else {}))

    # ------------------------------------------------- frontend metrics
    def dispatch_stall_cycles(self, pid: Optional[int] = None):
        """Cycles a tenant's frontend stream had arrived and still held
        undispatched instructions but was not granted dispatch — the
        per-tenant head-of-line metric.  ``pid=None`` returns the per-pid
        dict; a merged-frontend run charges everything to its one stream
        (keyed by pid 0).
        """
        pids = (self.streams.pids if self.streams is not None
                else (0,) * len(self.fe_stall))
        if pid is None:
            out: dict[int, int] = {}
            for p, s in zip(pids, self.fe_stall):
                out[p] = out.get(p, 0) + int(s)
            return out
        return sum(int(s) for p, s in zip(pids, self.fe_stall) if p == pid)

    def time_to_first_issue(self, pid: int) -> Optional[int]:
        """Cycles from ``pid``'s stream arrival to its first task issue
        (``None`` if the tenant never issued) — how long a late tenant
        waited before the scheduler actually started serving it.
        """
        issues = [row.issue for row in self.schedule
                  if row.pid == pid and row.issue >= 0]
        if not issues:
            return None
        arrival = (self.streams.arrival_of(pid)
                   if self.streams is not None else 0)
        return min(issues) - arrival

    def rs_occupancy_at_dispatch(self, pid: int) -> float:
        """Mean count of ``pid``'s own reservation-station-resident tasks
        at each of its dispatches (including the new one) — how deeply a
        tenant's stream queued behind itself inside the shared window.
        """
        rows = [(r.dispatch, r.issue) for r in self.schedule
                if r.pid == pid and not r.aborted and r.dispatch >= 0]
        if not rows:
            return 0.0
        # resident at cycle d: dispatched by d, not yet issued (RS issue
        # precedes dispatch within a cycle, so the earliest issue is d+1)
        occ = [sum(1 for d2, i2 in rows if d2 <= d and (i2 < 0 or i2 > d))
               for d, _ in rows]
        return sum(occ) / len(occ)

    def frontend_metrics(self, pid: int) -> dict:
        """The per-tenant frontend triple (dispatch-stall cycles, RS
        occupancy at dispatch, time-to-first-issue) as one dict."""
        return {
            "dispatch_stall_cycles": self.dispatch_stall_cycles(pid),
            "rs_occupancy_at_dispatch": self.rs_occupancy_at_dispatch(pid),
            "time_to_first_issue": self.time_to_first_issue(pid),
        }

    def table(self) -> str:
        """Human-readable per-task schedule."""
        lines = [f"{self.program} · {self.scheduler} · {self.backend} · "
                 f"{self.cycles} cycles · utilization "
                 f"{self.utilization:.1%}",
                 f"{'uid':>4} {'pid':>3} {'function':<13} {'dispatch':>8} "
                 f"{'issue':>8} {'complete':>9} {'broadcast':>9}"]
        for t in self.schedule:
            flag = "  (aborted)" if t.aborted else ""
            lines.append(f"{t.uid:>4} {t.pid:>3} {t.func_name:<13} "
                         f"{t.dispatch:>8} {t.issue:>8} {t.complete:>9} "
                         f"{t.broadcast:>9}{flag}")
        return "\n".join(lines)


@dataclasses.dataclass(frozen=True)
class FairnessReport:
    """Per-tenant slowdown of a shared run vs each tenant's solo run.

    ``weights`` carries each pid's priority weight under the run's
    :class:`SchedPolicy` so slowdown-vs-priority is one report: a working
    priority scheduler shows high-weight pids near slowdown 1.0 while
    low-weight pids absorb the queueing delay (:meth:`by_weight`).
    """
    slowdowns: dict[int, float]         # pid → shared/solo makespan ratio
    max_slowdown: float                 # fairness figure of merit
    mean_slowdown: float
    weights: dict[int, int] = dataclasses.field(default_factory=dict)
    #: per-pid frontend metrics (``Result.frontend_metrics``) when the
    #: shared run dispatched through per-tenant frontends; {} otherwise
    frontend: dict[int, dict] = dataclasses.field(default_factory=dict)

    def by_weight(self) -> dict[int, float]:
        """Mean slowdown per priority weight (descending weight order)."""
        acc: dict[int, list[float]] = {}
        for pid, s in self.slowdowns.items():
            acc.setdefault(self.weights.get(pid, 0), []).append(s)
        return {w: sum(v) / len(v)
                for w, v in sorted(acc.items(), reverse=True)}

    def table(self) -> str:
        lines = [f"{'pid':>4} {'weight':>7} {'slowdown':>9}"]
        for pid, s in sorted(self.slowdowns.items()):
            lines.append(f"{pid:>4} {self.weights.get(pid, 0):>7} {s:>9.3f}")
        lines.append(f" max {'':>7} {self.max_slowdown:>9.3f}")
        return "\n".join(lines)


def scenarios_per_second(n: int, wall_us: float) -> float:
    """Throughput of a measured run: ``n`` scenarios over ``wall_us`` host
    microseconds (0.0 for an unmeasured/zero wall) — the one scenarios/sec
    formula every benchmark and report shares."""
    return float(n) / (wall_us * 1e-6) if wall_us else 0.0


def _machine_rows(out: dict[str, Any]) -> tuple[TaskRow, ...]:
    return tuple(TaskRow(*row) for row in machine.schedule_tuple(out))


def _golden_rows(res: golden.Result) -> tuple[TaskRow, ...]:
    return tuple(TaskRow(*row) for row in res.schedule_tuple())


def _machine_result(name: str, scheduler: str, fu: tuple[int, ...],
                    out: dict[str, Any], wall_us: float,
                    pol: SchedPolicy, max_fu_per_class: int,
                    streams: Optional[StreamSet] = None) -> Result:
    """A :class:`Result` from one machine output dict (single scenario)."""
    halted = bool(out["halted"]) and not bool(out["overflow"])
    # keep only units that exist under fu (class-major, like golden)
    busy = np.asarray(out["fu_busy_cycles"]).reshape(NUM_FUNCS,
                                                     max_fu_per_class)
    busy_exist = tuple(int(busy[c, u]) for c in range(NUM_FUNCS)
                       for u in range(fu[c]))
    n_streams = len(streams) if streams is not None else 1
    fe_stall = tuple(int(x) for x in
                     np.asarray(out["fe_stall"]).ravel()[:n_streams])
    return Result(
        program=name, scheduler=scheduler, backend="jax", n_fu=fu,
        cycles=int(out["cycles"]), halted=halted,
        schedule=_machine_rows(out), spec_aborted=int(out["spec_aborted"]),
        stall_cycles=int(out["stall_cycles"]), fu_busy_cycles=busy_exist,
        wall_us=wall_us, raw=out, policy=pol, fe_stall=fe_stall,
        streams=streams)


# ---------------------------------------------------------------------------
# run
# ---------------------------------------------------------------------------
def run(program, *, scheduler: Union[str, SchedulerCosts] = "hts_spec",
        n_fu: Union[int, Sequence[int]] = 2, backend: str = "jax",
        params: HtsParams = HtsParams(), event_skip: bool = True,
        max_cycles: int = 5_000_000, max_prog: int = 256,
        max_fu_per_class: int = 16, check: bool = True,
        policy: Optional[SchedPolicy] = None, fu_cost=None,
        step_impl: str = "xla") -> Result:
    """Simulate ``program`` under one scheduler cost model.

    ``policy`` selects the RS arbitration (per-pid priority weights + FU
    quotas); when omitted, a policy attached to the program (e.g. by
    ``Program.merge(priorities=...)``) applies, then ``params.policy``.

    ``fu_cost`` gives FU instances heterogeneous latency: any form
    :func:`~repro.core.hts.costs.norm_fu_cost` accepts (a
    ``{class: row_or_scalar}`` mapping or full per-class table of integer
    multipliers — unit ``u`` of class ``c`` executes in
    ``FUNC_CYCLES[c] * fu_cost[c, u]`` cycles).  Resolution: explicit
    argument > ``params.fu_cost``.  Cost tables are runtime data to the
    compiled machine — sweeping them never recompiles.

    Raises :class:`SimulationError` (naming the program and scheduler) if the
    machine fails to drain within ``max_cycles`` — pass ``check=False`` to
    get the partial Result instead.
    """
    prep = _prepare(program)
    cost = _norm_costs(scheduler)
    fu = _norm_n_fu(n_fu)
    pol = _norm_policy(policy, prep, params)
    # per-tenant frontends: the stream table is runtime data, with the
    # frontend arbitration weights resolved from the effective policy
    stream_tab = (prep.streams.table(pol) if prep.streams is not None
                  else None)
    eff_cost = fu_cost if fu_cost is not None else params.fu_cost

    t0 = time.perf_counter()
    if backend == "jax":
        out = machine.simulate(prep.code, cost, params,
                               n_fu=np.asarray(fu, np.int32),
                               mem_init=prep.mem_init, effects=prep.effects,
                               event_skip=event_skip, max_cycles=max_cycles,
                               max_fu_per_class=max_fu_per_class,
                               max_prog=max_prog, policy=pol,
                               fu_cost=eff_cost, streams=stream_tab,
                               step_impl=step_impl)
        wall = (time.perf_counter() - t0) * 1e6
        result = _machine_result(prep.name, cost.name, fu, out, wall, pol,
                                 max_fu_per_class, prep.streams)
    elif backend == "golden":
        g = golden.run(prep.code, cost,
                       dataclasses.replace(params, n_fu=fu, policy=pol,
                                           fu_cost=fu_cost_tuple(eff_cost)),
                       prep.mem_init, prep.effects, max_cycles=max_cycles,
                       streams=stream_tab)
        wall = (time.perf_counter() - t0) * 1e6
        result = Result(
            program=prep.name, scheduler=cost.name, backend=backend,
            n_fu=fu, cycles=int(g.cycles), halted=bool(g.halted),
            schedule=_golden_rows(g), spec_aborted=int(g.spec_aborted),
            stall_cycles=int(g.stall_cycles),
            fu_busy_cycles=tuple(int(x) for x in g.fu_busy_cycles),
            wall_us=wall, raw=g, policy=pol,
            fe_stall=tuple(int(x) for x in g.fe_stall),
            streams=prep.streams)
    else:
        raise ValueError(f'backend must be "jax" or "golden", got {backend!r}')

    if check and not result.halted:
        raise SimulationError(
            f"program {prep.name!r} under scheduler {cost.name!r} "
            f"(backend={backend}, n_fu={fu}) did not halt within "
            f"{max_cycles} cycles — livelock, structural overflow, or "
            "max_cycles too small")
    return result


# ---------------------------------------------------------------------------
# run_many: the scenario axis — a population in one vmapped machine call
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)
class PopulationResult:
    """Stacked outcome of one batched population run.

    Array fields are scenario-major (``cycles[i]`` is scenario ``i``);
    ``self[i]`` materialises scenario ``i`` as an ordinary :class:`Result`
    (slicing the stacked trace arrays), so every per-scenario metric —
    ``schedule``, ``by_pid``, ``app_makespan``, ``fairness`` — works
    unchanged on population runs.
    """
    scheduler: str
    backend: str
    names: tuple[str, ...]
    n_fu: np.ndarray                   # (N, NUM_FUNCS)
    cycles: np.ndarray                 # (N,)
    halted: np.ndarray                 # (N,) bool (and not overflowed)
    wall_us: float                     # the one batched call, all scenarios
    max_fu_per_class: int
    policies: tuple[SchedPolicy, ...]
    raw: Any = dataclasses.field(repr=False, default=None)
    _results: Optional[tuple] = dataclasses.field(repr=False, default=None)
    #: per-scenario frontend stream sets (None entries = merged frontend)
    stream_sets: tuple = ()
    # the compiled-machine identity of this run (spec + shape bucket +
    # machine args), stashed by ``run_many`` so :meth:`trip_cost_us` can
    # re-enter the same compile bucket; None on the golden backend
    _spec: Any = dataclasses.field(repr=False, default=None)
    _max_prog: Optional[int] = dataclasses.field(repr=False, default=None)
    _margs: Any = dataclasses.field(repr=False, default=None)
    #: host time ``run_many`` spent packing the programs (the ``hts.pack``
    #: span), not part of ``wall_us``; 0.0 when handed a PackedPopulation
    pack_us: float = 0.0

    def __len__(self) -> int:
        return len(self.names)

    def __getitem__(self, i: int) -> Result:
        if self._results is not None:           # golden loop backend
            return self._results[i]
        out = {k: v[i] for k, v in self.raw.items()}
        fu = tuple(int(x) for x in self.n_fu[i])
        return _machine_result(self.names[i], self.scheduler, fu, out,
                               self.wall_us / max(len(self), 1),
                               self.policies[i], self.max_fu_per_class,
                               (self.stream_sets[i] if self.stream_sets
                                else None))

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    @property
    def all_halted(self) -> bool:
        return bool(np.asarray(self.halted).all())

    @property
    def steps(self) -> Optional[np.ndarray]:
        """Per-scenario while-loop step counts — the *measured* batching
        cost of each lane (a batch runs as long as its slowest lane's
        step count).  Feed this to ``batch.plan_chunks(profile=...)`` to
        re-chunk a long sweep from real costs instead of the
        instruction-count proxy.  ``None`` on the golden backend (the
        oracle has no step counter)."""
        if self.raw is None or "steps" not in self.raw:
            return None
        return np.asarray(self.raw["steps"])

    def scenarios_per_second(self, wall_us: Optional[float] = None) -> float:
        """Batched throughput (scenarios per host second).  ``wall_us``
        overrides this call's own wall — benchmarks pass their measured
        median so one formula serves every reported number."""
        return scenarios_per_second(
            len(self), self.wall_us if wall_us is None else wall_us)

    def trip_cost_us(self, budget: int = 128, reps: int = 5) -> float:
        """Median wall-clock per while-loop trip of this population's
        compiled machine (microseconds).

        The measurement re-enters the run's own compile bucket through
        the *resumable* machine: a fresh carry is advanced by exactly
        ``budget`` steps per lane (`run_slice` with a fixed step budget,
        ``block_until_ready`` around each call), ``reps`` times after one
        untimed warm-up, and the median wall divides by the trips the
        slice actually executed.  Because every lane runs the same
        budget from a fresh carry, trips = ``budget`` until a lane halts
        earlier — the returned figure is the population step body's
        per-trip cost at this lane width, the number
        ``benchmarks/stepwidth.py`` sweeps.  Requires the jax backend
        (raises on golden results).
        """
        import jax
        import jax.numpy as jnp
        if self._spec is None:
            raise ValueError("trip_cost_us requires a jax-backend "
                             "population run")
        rm = _population_slicer(self._spec, self._max_prog)
        args = [jnp.asarray(a) for a in self._margs]
        b = jnp.asarray(budget, jnp.int32)
        carry0 = jax.block_until_ready(rm.init(*args))
        jax.block_until_ready(rm.run_slice(carry0, *args, b))  # warm-up
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(rm.run_slice(carry0, *args, b))
            walls.append((time.perf_counter() - t0) * 1e6)
        trips = int(np.max(np.asarray(out["steps"])))
        return float(np.median(walls)) / max(trips, 1)

    def scenarios_per_sec(self) -> float:
        """Batched throughput of this call (scenarios per host second)."""
        return self.scenarios_per_second()

    def table(self) -> str:
        lines = [f"population · {self.scheduler} · {self.backend} · "
                 f"{len(self)} scenarios · {self.wall_us:.0f} us",
                 f"{'scenario':<28} {'cycles':>10} {'halted':>7}"]
        for i, nm in enumerate(self.names):
            lines.append(f"{nm:<28} {int(self.cycles[i]):>10} "
                         f"{str(bool(self.halted[i])):>7}")
        return "\n".join(lines)


@functools.partial(annotate_function, name="hts.run_many")
def run_many(programs, *,
             scheduler: Union[str, SchedulerCosts] = "hts_spec",
             n_fu: Union[int, Sequence] = 2, backend: str = "jax",
             params: HtsParams = HtsParams(), event_skip: bool = True,
             max_cycles: int = 5_000_000, max_prog: Optional[int] = None,
             max_fu_per_class: Optional[int] = None,
             policy=None, check: bool = True,
             devices: Optional[int] = None, fu_cost=None,
             step_impl: str = "xla") -> PopulationResult:
    """Simulate a population of programs as **one vmapped machine call**.

    ``programs`` is a sequence of anything :func:`run` accepts (or an
    already-packed :class:`~repro.core.hts.batch.PackedPopulation`, in
    which case ``n_fu``/``policy``/``max_prog``/``fu_cost`` come from the
    pack).  ``n_fu``, ``policy`` and ``fu_cost`` accept either one shared
    value or one entry per scenario — they are per-scenario arrays on the
    scenario axis (heterogeneous cost tables ride the same vmap axis as
    FU counts, so a cost sweep shares one compilation).

    One compilation serves every population of the same shape bucket
    (``batch.prog_bucket``); the batched call's wall-clock is the whole
    population's, which is what ``benchmarks/population.py`` measures
    against a Python loop of :func:`run`.

    ``devices=N`` shards the scenario axis across N devices
    (:mod:`~repro.core.hts.shard`): lanes are padded to a multiple of N
    (pad results dropped), each device runs the population machine's
    while loop on its own shard, and the results are lane-for-lane
    identical to the single-device path (``devices=None``, the default,
    which skips ``shard_map`` entirely; ``devices=1`` exercises the
    sharded code path on one device).  JAX backend only.

    ``backend="golden"`` runs the pure-Python oracle in a loop instead —
    same :class:`PopulationResult` surface, no batching (the differential
    baseline).

    ``step_impl`` selects the step-body lowering
    (:data:`~repro.core.hts.machine.STEP_IMPLS`): the restructured XLA
    form (default), the pre-restructure baseline, or the fused pallas
    kernels — all bit-identical, differentially pinned.  It is part of
    the compile key; the default value keeps the default path in the
    pre-existing compile bucket.
    """
    import jax
    import jax.numpy as jnp

    spans = Spans()
    if isinstance(programs, PackedPopulation):
        pop = programs
    else:
        with spans("hts.pack"):
            pop = batch.pack_population(programs, params=params, n_fu=n_fu,
                                        policy=policy, fu_cost=fu_cost,
                                        max_prog=max_prog)
    cost = _norm_costs(scheduler)

    if devices is not None and backend != "jax":
        raise ValueError(f'devices= requires backend="jax", got {backend!r}')
    if backend == "golden":
        with spans("hts.launch"):
            results = tuple(
                run(prep, scheduler=cost,
                    n_fu=tuple(int(x) for x in pop.n_fu[i]),
                    backend="golden", params=pop.params,
                    max_cycles=max_cycles, policy=pop.policies[i],
                    fu_cost=pop.fu_cost[i], check=check)
                for i, prep in enumerate(pop.preps))
        wall = spans.seconds("hts.launch") * 1e6
        return PopulationResult(
            scheduler=cost.name, backend="golden", names=pop.names,
            n_fu=pop.n_fu, cycles=np.asarray([r.cycles for r in results]),
            halted=np.asarray([r.halted for r in results]), wall_us=wall,
            max_fu_per_class=pop.widest_fu, policies=pop.policies,
            _results=results,
            stream_sets=tuple(p.streams for p in pop.preps),
            pack_us=spans.seconds("hts.pack") * 1e6)
    if backend != "jax":
        raise ValueError(f'backend must be "jax" or "golden", got {backend!r}')

    widest = pop.widest_fu
    if max_fu_per_class is None:
        # favour narrow compiled pools: population batches multiply every
        # per-unit state array by N scenarios
        max_fu_per_class = max(4, widest)
    elif widest > max_fu_per_class:
        raise ValueError(f"population n_fu {widest} exceeds "
                         f"max_fu_per_class {max_fu_per_class}")

    spec = machine.MachineSpec(params=pop.params, costs=cost,
                               event_skip=event_skip, max_cycles=max_cycles,
                               max_fu_per_class=max_fu_per_class,
                               step_impl=step_impl)
    runner = _runner_for(spec, pop.max_prog, devices)
    if devices is not None:
        from . import shard
        run_pop = shard.pad_lanes(pop, devices)
    else:
        run_pop = pop
    with spans("hts.launch"):
        out = runner(*(jnp.asarray(a) for a in run_pop.machine_args()))
        out = jax.tree.map(np.asarray, out)  # forces device completion
    wall = spans.seconds("hts.launch") * 1e6
    if len(run_pop) > len(pop):              # drop the shard-padding lanes
        out = {k: v[:len(pop)] for k, v in out.items()}

    halted = out["halted"] & ~out["overflow"]
    result = PopulationResult(
        scheduler=cost.name, backend="jax", names=pop.names, n_fu=pop.n_fu,
        cycles=out["cycles"], halted=halted, wall_us=wall,
        max_fu_per_class=max_fu_per_class, policies=pop.policies, raw=out,
        stream_sets=tuple(p.streams for p in pop.preps),
        _spec=spec, _max_prog=pop.max_prog,
        _margs=tuple(run_pop.machine_args()),
        pack_us=spans.seconds("hts.pack") * 1e6)
    if check and not result.all_halted:
        bad = [pop.names[i] for i in np.nonzero(~halted)[0]]
        raise SimulationError(
            f"population run under scheduler {cost.name!r}: scenarios "
            f"{bad} did not halt within {max_cycles} cycles — livelock, "
            "structural overflow, or max_cycles too small")
    return result


# ---------------------------------------------------------------------------
# sweep
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Strong-scaling sweep: ``cycles[scheduler][i]`` for ``n_fu_list[i]``.

    Population sweeps (``sweep`` over a sequence of programs) stack one
    more leading axis: ``cycles[scheduler][s, i]`` is scenario ``s``
    (named ``programs[s]``) at FU point ``i``.
    """
    program: str
    n_fu_list: tuple[tuple[int, ...], ...]
    schedulers: tuple[str, ...]
    cycles: dict[str, np.ndarray]
    wall_us: dict[str, float]           # total per scheduler (all FU points)
    programs: tuple[str, ...] = ()      # per-scenario names (population mode)

    @property
    def is_population(self) -> bool:
        return bool(self.programs)

    def speedup(self, scheduler: str, baseline: str) -> np.ndarray:
        """Per-point speedup of ``scheduler`` over ``baseline`` (same shape
        as ``cycles[...]`` — per (scenario, FU point) in population mode)."""
        return self.cycles[baseline] / self.cycles[scheduler]

    def table(self) -> str:
        head = "n_fu       " + " ".join(f"{s:>12}" for s in self.schedulers)
        lines = [f"{self.program} · strong scaling", head]
        for i, fu in enumerate(self.n_fu_list):
            k = fu[0] if len(set(fu)) == 1 else fu
            if self.is_population:      # summarise the scenario axis
                cells = [f"{float(self.cycles[s][:, i].mean()):>12.0f}"
                         for s in self.schedulers]
                lines.append(f"{str(k):<10} " + " ".join(cells))
            else:
                lines.append(f"{str(k):<10} " + " ".join(
                    f"{int(self.cycles[s][i]):>12}"
                    for s in self.schedulers))
        if self.is_population:
            lines.append(f"({len(self.programs)} scenarios; cells are "
                         "scenario means)")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the axes model: named vmap axes over the machine's 11-argument signature
# (ftab, p_len, n_fu, mem, eff, prio, quota, rs_cap, fu_cost, eft, streams)
# — see module docstring
# ---------------------------------------------------------------------------
SCENARIO_AXIS = (0,) * 11                             # a population, batched
SCENARIO_SHARED_FU_AXIS = (0, 0, None, 0, 0, 0, 0, 0, 0, 0, 0)  # pop × FU
N_FU_AXIS = (None, None, 0) + (None,) * 8            # Fig-10 FU scaling
POLICY_AXIS = (None, None, None, None, None, 0, 0, 0,
               None, 0, None)                        # policy sweep (incl. eft)


@functools.lru_cache(maxsize=32)
def _vmapped(spec: machine.MachineSpec, max_prog: int,
             axes: tuple = (N_FU_AXIS,)):
    """One jitted machine per ``(spec, max_prog, axes)`` static-shape bucket.

    ``axes`` is a stack of in_axes tuples, outermost first — e.g.
    ``(SCENARIO_SHARED_FU_AXIS, N_FU_AXIS)`` maps scenario-major over an
    inner FU grid.  Axes that stay ``None`` everywhere (like the policy
    tables in a plain FU sweep) still ride along as traced runtime data,
    so re-running with different policies never recompiles.
    """
    import jax
    fn = machine.make_machine(spec, max_prog)
    for in_axes in reversed(axes):
        fn = jax.vmap(fn, in_axes=in_axes)
    return jax.jit(fn)


@functools.lru_cache(maxsize=32)
def _population_runner(spec: machine.MachineSpec, max_prog: int):
    """The native scenario-axis machine (``machine.make_machine(...,
    population=True)``): one while loop for the whole batch, no per-lane
    carry select — strictly faster than ``_vmapped`` with SCENARIO_AXIS."""
    import jax
    return jax.jit(machine.make_machine(spec, max_prog, population=True))


def _runner_for(spec: machine.MachineSpec, max_prog: int,
                devices: Optional[int] = None):
    """The compiled population runner for one ``(spec, bucket, devices)``
    key — single-device native machine, or the ``shard_map``-sharded one.
    Both are module-cached, so the returned callable is the *same object*
    for every batch of the bucket; the serving engine (``serve.py``)
    leans on that for its recompilation accounting."""
    if devices is None:
        return _population_runner(spec, max_prog)
    from . import shard
    return shard.sharded_runner(spec, max_prog, devices)


@functools.lru_cache(maxsize=32)
def _population_slicer(spec: machine.MachineSpec, max_prog: int):
    """The resumable population machine for one ``(spec, bucket)``:
    ``init`` and ``run_slice`` jitted (``budget`` traced — slice-size
    sweeps never recompile), ``collect`` left as the plain host-friendly
    dict mapping (it also works row-wise on numpy snapshots of the
    carry, which is how ``serve.py`` harvests individual lanes)."""
    import jax
    rm = machine.make_machine(spec, max_prog, population=True,
                              resumable=True)
    return machine.ResumableMachine(init=jax.jit(rm.init),
                                    run_slice=jax.jit(rm.run_slice),
                                    collect=rm.collect)


def _slicer_for(spec: machine.MachineSpec, max_prog: int,
                devices: Optional[int] = None) -> machine.ResumableMachine:
    """The cached :class:`~repro.core.hts.machine.ResumableMachine` for a
    ``(spec, bucket, devices)`` key — the slice-and-refill counterpart of
    :func:`_runner_for` (same bucket discipline, same module-level
    caching, so serve's recompilation accounting covers it too)."""
    if devices is None:
        return _population_slicer(spec, max_prog)
    from . import shard
    return shard.sharded_slicer(spec, max_prog, devices)


@functools.partial(annotate_function, name="hts.sweep")
def sweep(program, *, n_fu=(1, 2, 4), schedulers=("naive", "hts_spec"),
          params: HtsParams = HtsParams(), event_skip: bool = True,
          max_cycles: int = 50_000_000, max_prog: Optional[int] = None,
          max_fu_per_class: Optional[int] = None,
          policy: Optional[SchedPolicy] = None, fu_cost=None) -> SweepResult:
    """Simulate ``program`` across FU configurations in one compiled,
    ``vmap``-batched machine per scheduler (the Fig-10 machinery).

    ``n_fu`` is a sequence of points; each point is an int (uniform per
    class) or a per-class tuple.  ``schedulers`` accepts names from
    ``costs.ALL_SCHEDULERS`` or :class:`SchedulerCosts` objects.
    ``policy`` applies one :class:`SchedPolicy` to every FU point (it is
    runtime data to the compiled machine, so changing it never recompiles);
    ``fu_cost`` likewise applies one heterogeneous cost table to every
    point — also runtime data, so a design-space explorer can sweep cost
    tables and FU mixes through one compilation.

    **Population mode**: handed a sequence of programs (or a
    :class:`~repro.core.hts.batch.PackedPopulation`), the scenario axis
    composes with the FU axis — one compiled machine evaluates the whole
    scenario × FU grid, and ``cycles[scheduler]`` has shape
    ``(n_scenarios, n_points)``.
    """
    import jax.numpy as jnp

    points = tuple(_norm_n_fu(k) for k in n_fu)
    widest = max(max(p) for p in points)
    n_fu_arr = jnp.asarray(points, jnp.int32)

    if _is_population(program):
        pop = (program if isinstance(program, PackedPopulation)
               else batch.pack_population(program, params=params,
                                          policy=policy, fu_cost=fu_cost,
                                          max_prog=max_prog))
        name = f"<population of {len(pop)}>"
        # per-scenario n_fu from the pack is overridden by the swept axis;
        # everything else (images, policies) is per-scenario
        args = [jnp.asarray(a) for a in pop.machine_args()]
        args[2] = n_fu_arr
        axes: tuple = (SCENARIO_SHARED_FU_AXIS, N_FU_AXIS)
        run_prog = pop.max_prog
        params_c = pop.params
        point_names = [f"{pop.names[s]} @ {points[i]}"
                       for s in range(len(pop)) for i in range(len(points))]
    else:
        prep = _prepare(program)
        pol = _norm_policy(policy, prep, params)
        name = prep.name
        run_prog = 64 if max_prog is None else max_prog
        ftab, p_len = machine.pack_program(prep.code, run_prog)
        mem, eff = machine.images(params, prep.mem_init, prep.effects)
        stream_tab = (prep.streams.table(pol) if prep.streams is not None
                      else batch.StreamSet.single(p_len).table())
        eff_cost = fu_cost if fu_cost is not None else params.fu_cost
        args = [jnp.asarray(ftab), jnp.asarray(p_len, jnp.int32), n_fu_arr,
                jnp.asarray(mem), jnp.asarray(eff),
                jnp.asarray(pol.weight_array(), jnp.int32),
                jnp.asarray(pol.quota_array(), jnp.int32),
                jnp.asarray(pol.rs_cap_array(), jnp.int32),
                jnp.asarray(norm_fu_cost(eff_cost), jnp.int32),
                jnp.asarray(1 if pol.issue_mode == "eft" else 0, jnp.int32),
                jnp.asarray(stream_tab, jnp.int32)]
        axes = (N_FU_AXIS,)
        # policy + cost tables are runtime data — keep them out of the
        # compilation key
        params_c = dataclasses.replace(params, policy=SchedPolicy(),
                                       fu_cost=None)
        point_names = [f"{name} @ {p}" for p in points]

    if max_fu_per_class is None:
        max_fu_per_class = max(16, widest)
    elif widest > max_fu_per_class:
        raise ValueError(f"n_fu point {widest} exceeds max_fu_per_class "
                         f"{max_fu_per_class}")

    cost_objs = [_norm_costs(s) for s in schedulers]
    cycles: dict[str, np.ndarray] = {}
    wall: dict[str, float] = {}
    for cost in cost_objs:
        spec = machine.MachineSpec(params=params_c, costs=cost,
                                   event_skip=event_skip,
                                   max_cycles=max_cycles,
                                   max_fu_per_class=max_fu_per_class)
        runner = _vmapped(spec, run_prog, axes)
        spans = Spans()
        with spans("hts.launch", scheduler=cost.name):
            out = runner(*args)
            cyc = np.asarray(out["cycles"])
        wall[cost.name] = spans.seconds("hts.launch") * 1e6
        ok = np.asarray(out["halted"]) & ~np.asarray(out["overflow"])
        if not ok.all():
            bad = [point_names[i] for i in np.nonzero(~ok.ravel())[0]]
            raise SimulationError(
                f"sweep of {name!r} under {cost.name!r}: points "
                f"{bad} did not halt within {max_cycles} cycles")
        cycles[cost.name] = cyc
    return SweepResult(program=name, n_fu_list=points,
                       schedulers=tuple(c.name for c in cost_objs),
                       cycles=cycles, wall_us=wall,
                       programs=(pop.names if _is_population(program)
                                 else ()))


# ---------------------------------------------------------------------------
# compare: differential runner (golden vs machine, event-skip on and off)
# ---------------------------------------------------------------------------
class MismatchError(AssertionError):
    """Two backends produced different schedules for the same program."""


@dataclasses.dataclass(frozen=True)
class CompareReport:
    """Outcome of :func:`compare`: per-scheduler agreed-upon results.

    ``results[scheduler]`` is the golden-backend :class:`Result` (the oracle;
    the JAX machine runs — event-skip on *and* off — were verified
    schedule-identical to it).  ``n_modes`` counts the executions per
    scheduler (3: golden, jax+skip, jax-noskip).
    """
    program: str
    schedulers: tuple[str, ...]
    results: dict[str, Result]
    n_modes: int = 3

    def cycles(self, scheduler: str) -> int:
        return self.results[scheduler].cycles


def _first_diff(a: list[tuple], b: list[tuple]) -> str:
    if len(a) != len(b):
        return f"row counts differ: {len(a)} vs {len(b)}"
    for i, (ra, rb) in enumerate(zip(a, b)):
        if ra != rb:
            return f"first differing row {i}: {ra} vs {rb}"
    return "schedules equal"


@dataclasses.dataclass(frozen=True, eq=False)
class PopulationCompareReport:
    """Outcome of a population :func:`compare`: every scenario agreed.

    For each scheduler, the whole population ran as one vmapped machine
    batch per event-skip mode and was checked scenario-by-scenario against
    a golden loop; ``cycles[scheduler]`` holds the agreed per-scenario
    cycle counts.
    """
    names: tuple[str, ...]
    schedulers: tuple[str, ...]
    cycles: dict[str, np.ndarray]       # scheduler -> (N,) agreed cycles
    n_modes: int = 3

    def __len__(self) -> int:
        return len(self.names)


def compare_population(programs, *,
                       schedulers: Sequence[Union[str, SchedulerCosts]] =
                       ("naive", "hts_nospec", "hts_spec"),
                       n_fu: Union[int, Sequence] = 2,
                       params: HtsParams = HtsParams(),
                       max_cycles: int = 5_000_000,
                       max_prog: Optional[int] = None,
                       max_fu_per_class: Optional[int] = None,
                       policy=None, fu_cost=None,
                       devices: Optional[int] = None,
                       step_impl: str = "xla") -> PopulationCompareReport:
    """Differential verification of a whole population: one vmapped machine
    batch per (scheduler, event-skip mode), checked scenario-by-scenario
    against a golden loop.  Raises :class:`MismatchError` naming the
    scenario, scheduler and mode on the first divergence.

    ``devices=N`` routes the *machine-side* runs through the sharded
    ``shard_map`` path (the golden loop stays host-side and unsharded),
    so device sharding is differentially verified lane-for-lane by the
    same oracle as everything else.
    """
    pop = (programs if isinstance(programs, PackedPopulation)
           else batch.pack_population(programs, params=params, n_fu=n_fu,
                                      policy=policy, fu_cost=fu_cost,
                                      max_prog=max_prog))
    if max_fu_per_class is None:
        max_fu_per_class = max(4, pop.widest_fu)
    cycles: dict[str, np.ndarray] = {}
    names = []
    for scheduler in schedulers:
        cost = _norm_costs(scheduler)
        names.append(cost.name)
        gold = run_many(pop, scheduler=cost, backend="golden",
                        max_cycles=max_cycles)
        gold_rows = [g.schedule_tuple() for g in gold]
        for event_skip in (True, False):
            m = run_many(pop, scheduler=cost, event_skip=event_skip,
                         max_cycles=max_cycles,
                         max_fu_per_class=max_fu_per_class, devices=devices,
                         step_impl=step_impl)
            mode = f"jax event_skip={'on' if event_skip else 'off'}"
            for i in range(len(pop)):
                if int(m.cycles[i]) != int(gold.cycles[i]):
                    raise MismatchError(
                        f"scenario {i} ({pop.names[i]!r}) under "
                        f"{cost.name!r}: {mode} ran {int(m.cycles[i])} "
                        f"cycles, golden ran {int(gold.cycles[i])}")
                mi = m[i].schedule_tuple()
                if mi != gold_rows[i]:
                    raise MismatchError(
                        f"scenario {i} ({pop.names[i]!r}) under "
                        f"{cost.name!r}: {mode} schedule differs from "
                        f"golden — {_first_diff(mi, gold_rows[i])}")
        cycles[cost.name] = np.asarray(gold.cycles)
    return PopulationCompareReport(names=pop.names,
                                   schedulers=tuple(names), cycles=cycles)


def compare(program, *,
            schedulers: Sequence[Union[str, SchedulerCosts]] =
            ("naive", "hts_nospec", "hts_spec"),
            n_fu: Union[int, Sequence[int]] = 2,
            params: HtsParams = HtsParams(),
            max_cycles: int = 5_000_000, max_prog: Optional[int] = None,
            max_fu_per_class: Optional[int] = None,
            policy: Optional[SchedPolicy] = None, fu_cost=None,
            step_impl: str = "xla"):
    """Differential execution: golden oracle vs the compiled JAX machine with
    event-skip **on and off**, for every scheduler cost model.

    ``fu_cost`` threads a heterogeneous per-(class, unit) cost table through
    every execution, so heterogeneous latency and the ``eft`` arbiter are
    differentially verified by the same machinery as everything else.

    ``policy`` applies one :class:`SchedPolicy` to every execution (defaults
    to the program-attached policy, e.g. from ``Program.merge(priorities=
    ...)``) — so priority/quota arbitration is differentially verified by
    the same machinery as the baseline age-order arbiter.

    Raises :class:`MismatchError` (naming program, scheduler and mode) on the
    first schedule-tuple or cycle-count disagreement; returns a
    :class:`CompareReport` of the agreed results otherwise.  This is the
    fuzzing workhorse: any scheduling-semantics divergence between the two
    simulators — or between the event-skip fast path and the cycle-by-cycle
    reference — surfaces as a mismatch on some generated scenario.

    **Population mode**: handed a sequence of programs (or a
    :class:`~repro.core.hts.batch.PackedPopulation`), delegates to
    :func:`compare_population` — the machine side then runs as one vmapped
    batch per mode and a :class:`PopulationCompareReport` is returned.
    """
    if _is_population(program):
        return compare_population(
            program, schedulers=schedulers, n_fu=n_fu, params=params,
            max_cycles=max_cycles, max_prog=max_prog,
            max_fu_per_class=max_fu_per_class, policy=policy,
            fu_cost=fu_cost, step_impl=step_impl)
    prep = _prepare(program)
    if max_prog is None:
        max_prog = 256
    fu = _norm_n_fu(n_fu)
    if max_fu_per_class is None:
        # size the compiled FU pool to the request: the no-event-skip runs
        # tick every cycle, and per-cycle cost scales with the pool width
        max_fu_per_class = max(4, max(fu))
    results: dict[str, Result] = {}
    names = []
    for scheduler in schedulers:
        cost = _norm_costs(scheduler)
        names.append(cost.name)
        g = run(prep, scheduler=cost, n_fu=fu, backend="golden",
                params=params, max_cycles=max_cycles, max_prog=max_prog,
                policy=policy, fu_cost=fu_cost)
        gold_rows = g.schedule_tuple()
        for event_skip in (True, False):
            m = run(prep, scheduler=cost, n_fu=fu, backend="jax",
                    params=params, event_skip=event_skip,
                    max_cycles=max_cycles, max_prog=max_prog,
                    max_fu_per_class=max_fu_per_class, policy=policy,
                    fu_cost=fu_cost, step_impl=step_impl)
            mode = f"jax event_skip={'on' if event_skip else 'off'}"
            if m.cycles != g.cycles:
                raise MismatchError(
                    f"{prep.name!r} under {cost.name!r}: {mode} ran "
                    f"{m.cycles} cycles, golden ran {g.cycles}")
            if m.schedule_tuple() != gold_rows:
                raise MismatchError(
                    f"{prep.name!r} under {cost.name!r}: {mode} schedule "
                    f"differs from golden — "
                    f"{_first_diff(m.schedule_tuple(), gold_rows)}")
        results[cost.name] = g
    return CompareReport(program=prep.name, schedulers=tuple(names),
                         results=results)


__all__ = ["run", "run_many", "sweep", "compare", "compare_population",
           "Result", "PopulationResult", "SweepResult", "TaskRow",
           "FairnessReport", "CompareReport", "PopulationCompareReport",
           "MismatchError", "SimulationError", "SchedPolicy",
           "PackedPopulation", "ALL_SCHEDULERS", "STEP_IMPLS",
           "scenarios_per_second"]
