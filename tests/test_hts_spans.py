"""Spans, scopes and the serving record: ``Server.report(since=)`` under a
manual clock, each launch's host phases against its wall, the step
phases' ``jax.named_scope`` names in the lowered machine, and the
``hts.serve.*`` spans in a captured CPU profile."""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hts
from repro.core.hts import api, batch, machine
from repro.core.hts.builder import Program
from repro.core.hts.serve import LAUNCH_PHASES
from repro.core.hts.spans import Spans

#: a binary fraction, so sums of clock steps are exact
TICK = 2.0 ** -6
#: the phases each step wraps in a scope, and the trace writes' own
PHASES = ("step_top", "fu_exec", "cdb_enqueue", "memread_tick", "cdb_grant",
          "branch_core", "abort_traces", "rs_issue", "frontend_core",
          "dispatch_traces", "step_bottom", "next_dt", "trace_write")


def _tiny(name, n_tasks=2):
    p = Program(name, region_base=0x100)
    prev = p.input(0x10, 4, "frame")
    for i in range(n_tasks):
        prev = p.task("vector_dot", in_=prev, out=4, in_size=4, tid=i)
    return p


def _device_time(srv, clock, per_slice):
    """Each ``run_slice`` of the sliced server ``srv`` advances ``clock``
    by ``per_slice``: the device's time, on the manual clock."""
    real = srv._runner

    def runner(key):
        rm = real(key)

        def run_slice(*args):
            clock.advance(per_slice)
            return rm.run_slice(*args)
        return dataclasses.replace(rm, run_slice=run_slice)
    srv._runner = runner


# ---------------------------------------------------------------------------
# the spans helper
# ---------------------------------------------------------------------------
def test_spans_rows_and_seconds():
    clock = hts.ManualClock()
    spans = Spans(clock.now)
    with spans("outer", reason="drain"):
        with spans("inner"):
            clock.advance(TICK)
        with spans("inner"):
            clock.advance(2 * TICK)
    assert spans.rows == [("inner", 0.0, TICK), ("inner", TICK, 3 * TICK),
                          ("outer", 0.0, 3 * TICK)]
    assert spans.seconds("inner") == 3 * TICK
    assert spans.seconds("absent") == 0


# ---------------------------------------------------------------------------
# Server.report(since=) on a manual clock
# ---------------------------------------------------------------------------
def test_report_since_manual_clock():
    """Two sliced launches of a two-lane bucket; every slice takes one
    tick of the manual clock.  Queue wait, time in a lane, launch delay,
    wall and reason come out exactly, and ``since`` keeps only the
    second launch and its requests."""
    clock = hts.ManualClock()
    srv = hts.serve(max_batch=2, deadline=3 * TICK, slice_steps=4096,
                    clock=clock)
    _device_time(srv, clock, TICK)
    a, b = srv.submit(_tiny("a")), srv.submit(_tiny("b"))
    clock.advance(4 * TICK)
    assert srv.poll() == 1                       # aged past the deadline
    assert a.done() and b.done()
    clock.advance(TICK)
    since = clock.now()                          # 6 ticks: 1 in the slice
    futs = [srv.submit(_tiny(n)) for n in "cde"]
    clock.advance(2 * TICK)
    assert srv.drain() == 1
    assert all(f.result(timeout=0).halted for f in futs)

    first, second = srv._batch_rows
    assert (first.reason, first.start, first.end) == ("deadline", 4 * TICK,
                                                      5 * TICK)
    # c and d fill both lanes; e refills the first lane freed
    assert (second.reason, second.start, second.end) == ("drain", 8 * TICK,
                                                         10 * TICK)

    rep = srv.report(since=since)
    assert (rep.requests, rep.batches) == (3, 1)
    assert rep.submit_hold_mean == rep.submit_hold_max == 0.0
    # waits: c, d 2 ticks (submit -> first slice), e 3 ticks
    assert rep.queue_wait_p50 == 2 * TICK
    assert rep.queue_wait_p95 == pytest.approx(2.9 * TICK, rel=1e-12)
    assert rep.in_lane_mean == rep.in_lane_p50 == rep.in_lane_p95 == TICK
    assert rep.slices_p50 == rep.slices_p95 == 1
    # three requests pending at the launch, which ran two slices
    assert rep.launch_queue_mean == rep.launch_queue_max == 3
    assert rep.launch_slices_mean == rep.launch_slices_max == 2
    assert rep.launch_delay_mean == rep.launch_delay_max == 2 * TICK
    assert rep.launch_wall_mean == rep.launch_wall_max == 2 * TICK
    assert rep.phase_s["slice"] == 2 * TICK
    assert rep.host_share == 0.0                 # host work took no time
    assert rep.launches_by_reason == {"deadline": 0, "drain": 1,
                                      "pressure": 0, "full": 0}
    (stats,) = rep.per_bucket.values()
    assert stats.mean_wait == pytest.approx(7 / 3 * TICK, rel=1e-12)
    assert stats.occupancy == 0.75               # 2 lanes, then 1 of 2

    whole = srv.report()
    assert (whole.requests, whole.batches) == (5, 2)
    assert whole.launches_by_reason["deadline"] == 1
    assert whole.launch_delay_max == 4 * TICK    # a and b aged 4 ticks
    assert whole.launch_wall_mean == 1.5 * TICK
    assert whole.launch_queue_mean == 2.5 and whole.launch_slices_max == 2
    assert "launches by reason" in whole.table()
    empty = srv.report(since=clock.now() + 1)
    assert empty.requests == 0 and np.isnan(empty.queue_wait_p50)


def test_report_submit_hold_manual_clock():
    """A submit whose deadline flush launches holds its caller: the
    request is enqueued (its queue wait starts) only after that launch,
    and the hold, the wait and the time in a lane add up to the
    request's whole time from ``submit()`` to its result."""
    clock = hts.ManualClock()
    srv = hts.serve(max_batch=2, deadline=3 * TICK, slice_steps=4096,
                    clock=clock)
    _device_time(srv, clock, TICK)
    a, b = srv.submit(_tiny("a")), srv.submit(_tiny("b"))
    clock.advance(4 * TICK)
    c = srv.submit(_tiny("c"))               # flushes a and b: one slice
    assert a.done() and b.done() and not c.done()
    assert srv.drain() == 1
    assert c.result(timeout=0).halted
    rep = srv.report()
    assert rep.submit_hold_max == TICK
    assert rep.submit_hold_mean == pytest.approx(TICK / 3, rel=1e-12)
    # a and b waited out the deadline; c was enqueued at 5 ticks and
    # launched at once by the drain
    assert rep.queue_wait_p50 == 4 * TICK
    (stats,) = rep.per_bucket.values()
    assert stats.mean_wait == pytest.approx(8 / 3 * TICK, rel=1e-12)
    assert rep.launches_by_reason["deadline"] == 1
    assert rep.launch_delay_max == 4 * TICK
    for r in srv._req_rows:
        assert r.done - r.enter == (r.submit - r.enter) + (r.fill - r.submit) \
            + (r.done - r.fill)
    assert [r.done - r.enter for r in srv._req_rows] == [5 * TICK,
                                                          5 * TICK, 2 * TICK]


def test_report_launch_reasons_from_submit():
    """Static mode launches on a full batch from ``submit``; a sliced
    server launches there only under queue pressure."""
    srv = hts.serve(max_batch=2, deadline=99.0, clock=hts.ManualClock())
    futs = [srv.submit(_tiny(n)) for n in "xyz"]
    assert futs[0].done() and not futs[2].done()
    srv.drain()
    assert [b.reason for b in srv._batch_rows] == ["full", "drain"]
    assert srv.report().launch_slices_mean == 1   # run to completion
    sliced = hts.serve(max_batch=2, max_queue=2, deadline=99.0,
                       slice_steps=4096, clock=hts.ManualClock())
    f = [sliced.submit(_tiny(n)) for n in "xy"]
    assert not f[0].done()                      # the queue is the reservoir
    sliced.submit(_tiny("w"))                   # full queue: launches inline
    assert [b.reason for b in sliced._batch_rows] == ["pressure"]


@pytest.mark.parametrize("slice_steps", [None, 8])
def test_launch_phases_within_wall(slice_steps):
    """On the system clock, the host phases of every launch (pack, slice,
    harvest, refill) add up to no more than the launch's wall, and each
    request's wait and time in a lane are ordered."""
    srv = hts.serve(max_batch=2, deadline=99.0, slice_steps=slice_steps)
    futs = [srv.submit(_tiny(f"p{i}", 1 + i % 3)) for i in range(5)]
    srv.drain()
    assert all(f.result(timeout=0).halted for f in futs)
    assert srv._batch_rows
    for b in srv._batch_rows:
        assert set(b.phase_s) == set(LAUNCH_PHASES)
        assert sum(b.phase_s.values()) <= b.end - b.start
        assert b.phase_s["slice"] > 0 and b.start >= b.oldest
    for r in srv._req_rows:
        assert r.enter <= r.submit <= r.fill <= r.done and r.slices >= 1
    rep = srv.report()
    assert 0.0 <= rep.host_share <= 1.0
    assert rep.requests == 5
    assert rep.slices_p95 >= rep.slices_p50 >= 1
    assert rep.launch_slices_max >= rep.launch_slices_mean >= 1


# ---------------------------------------------------------------------------
# named scopes on the machine step
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["xla", "xla_base"])
def test_step_phases_named_in_hlo(impl):
    """Every step phase, and the trace writes, name operations in the
    lowered population machine's op metadata."""
    params = hts.HtsParams(max_tasks=32, cdb_entries=16)
    pop = batch.pack_population([_tiny("a"), _tiny("b", 3)], params=params,
                                n_fu=2)
    spec = machine.MachineSpec(params=pop.params,
                               costs=api._norm_costs("hts_spec"),
                               max_fu_per_class=4, step_impl=impl)
    fn = jax.jit(machine.make_machine(spec, pop.max_prog, population=True))
    hlo = fn.lower(*(jnp.asarray(a) for a in pop.machine_args())).as_text(
        dialect="hlo", debug_info=True)
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    scopes = {part.removeprefix("vmap(").removesuffix(")")
              for path in paths for part in path.split("/")}
    assert set(PHASES) <= scopes
    assert any("step_bottom)/next_dt" in p for p in paths)


# ---------------------------------------------------------------------------
# the serving spans in a profile
# ---------------------------------------------------------------------------
def test_serve_spans_in_cpu_profile(tmp_path):
    """A two-request sliced serve under ``jax.profiler``: the launch span
    carries its arguments, and its pack, slice and harvest spans lie
    inside it, on the profiler's clock."""
    from jax.profiler import ProfileData
    srv = hts.serve(max_batch=2, deadline=99.0, slice_steps=8)
    futs = [srv.submit(_tiny("a")), srv.submit(_tiny("b", 3))]
    srv.drain()                                  # compile outside the trace
    futs = [srv.submit(_tiny("a")), srv.submit(_tiny("b", 3))]
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.drain()
    finally:
        jax.profiler.stop_trace()
    assert all(f.result(timeout=0).halted for f in futs)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("hts.serve.")]
    launches = [e for e in events if e[0] == "hts.serve.launch"]
    assert len(launches) == 1
    _, lo, hi, args = launches[0]
    assert args["reason"] == "drain" and args["lanes"] == 2
    inner = [e for e in events if e[0] != "hts.serve.launch"]
    assert {e[0] for e in inner} >= {"hts.serve.pack", "hts.serve.slice",
                                     "hts.serve.harvest"}
    assert all(lo <= s <= e <= hi for _, s, e, _ in inner)
