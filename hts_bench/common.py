"""Pieces the drivers share: host spans, the configuration's parameters,
the reference check and its numbers."""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from hts_bench import reference


class Spans:
    """The benchmark's host spans: each goes into the profiler's trace
    (``jax.profiler.TraceAnnotation``, a no-op unless a trace is on) and
    into an in-memory list on the host clock."""

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self.rows: list = []         # (name, start, end), perf_counter s

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with self._annotation(name):
            yield
        self.rows.append((name, t0, time.perf_counter()))


@dataclasses.dataclass(frozen=True)
class Check:
    """One number the comparison produced, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def hts_params(cfg: dict, traffic: dict):
    """``hts.HtsParams`` as the configuration states the machine, with the
    trace capacities the traffic file sizes for its scenarios."""
    from repro.core import hts
    return hts.HtsParams(**cfg["hts"], **traffic.get("capacities", {}))


def func_cycles(cfg: dict) -> list:
    """Table-II latency of each function class, indexed by accelerator id."""
    out = [0] * len(cfg["functions"])
    for acc, cycles in cfg["functions"].values():
        out[acc] = cycles
    return out


def per_class(n_fu, cfg: dict) -> tuple:
    return ((int(n_fu),) * len(cfg["functions"])
            if np.ndim(n_fu) == 0 else tuple(int(k) for k in n_fu))


def expect(built, cfg: dict, scheduler: str, n_fu, *, sched=None,
           policy=None):
    """The reference's outcome for one built program under ``policy``
    (plain data, ``None`` for age order; ``sched`` replaces the
    configuration's cost model of ``scheduler``)."""
    return reference.simulate(
        built.code, built.mem_init, built.effects, hw=cfg["hts"],
        sched=sched or cfg["schedulers"][scheduler],
        func_cycles=func_cycles(cfg), n_fu=per_class(n_fu, cfg),
        policy=policy)


def differs(got_cycles, got_halted, got_rows, want) -> bool:
    """Whether one answer disagrees with the reference's: its cycle count,
    whether it halted, and (where the entry returns them) its rows."""
    if int(got_cycles) != want.cycles or bool(got_halted) != want.halted:
        return True
    if got_rows is None:
        return False
    return [tuple(int(x) for x in r) for r in got_rows] != \
        [tuple(int(x) for x in r) for r in want.rows]


def sample(rng: np.random.Generator, n_items: int, k: int,
           must: int | None = None) -> list:
    """``k`` distinct indices of ``n_items`` drawn from ``rng``, with
    ``must`` among them."""
    pick = set(rng.choice(n_items, size=min(k, n_items),
                          replace=False).tolist())
    if must is not None and must not in pick:
        pick.discard(next(iter(pick)))
        pick.add(must)
    return sorted(pick)
