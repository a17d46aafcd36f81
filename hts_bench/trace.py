"""Profiler trace capture and its reduction to device time.

A traced run wraps its window in ``jax.profiler`` and in one host span,
``bench.window``.  :func:`load` reads the ``.xplane.pb`` the profiler wrote
into a :class:`Timeline`: the device operations of each chip and the
benchmark's host spans (``bench.*``), on the profiler's one clock.  The
functions below it are plain interval arithmetic over that timeline, kept
apart so that ``hts_bench/tests/test_trace.py`` checks them on a small
recorded trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import tempfile

#: host spans the benchmark writes (``jax.profiler.TraceAnnotation``)
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: lines of a device plane: one event per executed operation, and one per
#: executed program (a program's event also spans its while loops' control
#: between operations)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: device plane statistic: trace events the profiler had no room for
DROPPED_STAT = "dropped_traces"
#: label of an idle gap during which no benchmark span was open
NO_SPAN = "no_bench_span"


@dataclasses.dataclass
class Timeline:
    """Device work per chip and host spans, in nanoseconds."""
    ops: dict            # device plane name -> [(op name, start, end)]
    spans: list          # [(span name, start, end)]
    modules: dict = dataclasses.field(default_factory=dict)
    #                      device plane name -> [(program, start, end)]
    dropped: int = 0     # events the profiler dropped, over all chips

    def window(self) -> tuple[float, float]:
        w = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if not w:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        return w[0]


def options():
    """Profiler options: no Python call tracing (the benchmark's spans are
    ``TraceAnnotation``s, which the host tracer keeps)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@contextlib.contextmanager
def capture():
    """Trace what runs inside; yields a list that holds the
    :class:`Timeline` once the block has ended."""
    import jax
    box: list = []
    with tempfile.TemporaryDirectory(prefix="hts_bench_trace_") as d:
        jax.profiler.start_trace(d, profiler_options=options())
        try:
            yield box
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one xplane file, found {files}")
        box.append(load(files[0]))


def load(path: str) -> Timeline:
    from jax.profiler import ProfileData
    lines = {OPS_LINE: {}, MODULES_LINE: {}}
    spans: list = []
    dropped = 0
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        if device:
            dropped += sum(int(v) for k, v in (plane.stats or ())
                           if k == DROPPED_STAT)
        for line in plane.lines:
            if device and line.name in lines:
                lines[line.name][plane.name] = [
                    (e.name.split(" ")[0], e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events]
            elif not device:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    ops = {k: v for k, v in lines[OPS_LINE].items() if v}
    return Timeline(ops, spans,
                    {k: v for k, v in lines[MODULES_LINE].items() if v},
                    dropped)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def union(intervals, lo: float, hi: float) -> list:
    """Merged, sorted intervals, clipped to [lo, hi]."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged, lo: float, hi: float) -> float:
    """Length of [lo, hi] that the merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo: float, hi: float) -> list:
    """The stretches of [lo, hi] that the merged intervals leave open."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost_span(spans, t: float) -> str:
    """Name of the shortest benchmark span (other than the window) open at
    time ``t``: what the host was doing then."""
    open_ = [(e - s, n) for n, s, e in spans
             if s <= t < e and n != WINDOW_SPAN]
    return min(open_)[1] if open_ else NO_SPAN


@dataclasses.dataclass
class DeviceTime:
    busy_s: float              # union of device events, mean over chips
    window_s: float
    top_ops: list              # [[op name, seconds]], most time first
    idle_gaps: list            # [[host span name, seconds]], longest first
    cut: bool = False          # the trace dropped events: window_s ends
    #                            where its kept device events end


def reduce(tl: Timeline, top: int = 10) -> DeviceTime:
    """Device time in the traced window.  A chip is busy while one of its
    programs or operations runs; busy time is averaged over the chips.

    A machine trip is thousands of tiny operations, and the TPU profiler
    keeps only the first few million events of a trace (about 2 s of the
    machine's work) and counts the rest as dropped.  Where it dropped any,
    the window is cut where its kept device events end: busy time, idle
    gaps and top operations are read over the stretch the trace covers,
    and ``window_s`` is that stretch's length."""
    lo, hi = tl.window()
    cut = tl.dropped > 0
    if cut:
        hi = min(hi, max((e for evs in [*tl.ops.values(),
                                        *tl.modules.values()]
                          for _, s, e in evs if s < hi), default=hi))
    devices = sorted(set(tl.ops) | set(tl.modules))
    merged = {dev: union([(s, e) for _, s, e in
                          tl.ops.get(dev, []) + tl.modules.get(dev, [])],
                         lo, hi)
              for dev in devices}
    merged = {dev: m for dev, m in merged.items() if m}
    if not merged:
        raise ValueError("the trace holds no device operation")
    busy = sum(covered(m, lo, hi) for m in merged.values()) / len(merged)
    per_op: dict = {}
    for evs in tl.ops.values():
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] = per_op.get(name, 0.0) + d
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = []
    for m in merged.values():
        for s, e in gaps(m, lo, hi):
            idle.append((e - s, innermost_span(tl.spans, (s + e) / 2)))
    idle.sort(reverse=True)
    return DeviceTime(
        busy_s=busy * 1e-9, window_s=(hi - lo) * 1e-9,
        top_ops=[[n, d * 1e-9] for n, d in ops],
        idle_gaps=[[n, d * 1e-9] for d, n in idle[:top]], cut=cut)
