"""The trace reduction: busy union, idle share, idle gaps by host span,
top operations, and the per-layer readers over them."""
import json
import pathlib

import pytest

from hts_bench import run, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _timeline():
    # window 0..100; device ops overlap (union 10..40, 50..60, 90..100)
    ops = {"/device:TPU:0": [("fusion.1", 10, 30), ("fusion.2", 20, 40),
                             ("while", 50, 60), ("fusion.1", 90, 110)]}
    spans = [("bench.window", 0, 100), ("bench.call", 5, 65),
             ("bench.fetch", 62, 64), ("bench.call", 80, 100)]
    return trace.Timeline(ops, spans)


def test_union_gaps_and_covered():
    m = trace.union([(10, 30), (20, 40), (50, 60), (90, 110)], 0, 100)
    assert m == [[10, 40], [50, 60], [90, 100]]
    assert trace.covered(m, 0, 100) == 50
    assert trace.covered(m, 35, 55) == 10
    assert trace.gaps(m, 0, 100) == [(0, 10), (40, 50), (60, 90)]


def test_reduce_busy_idle_and_breakdown():
    dt = trace.reduce(_timeline())
    assert dt.window_s == pytest.approx(100e-9)
    assert dt.busy_s == pytest.approx(50e-9)
    assert dt.top_ops[0] == ["fusion.1", pytest.approx(30e-9)]
    # longest gap first, named by the innermost open span at its midpoint
    assert dt.idle_gaps[0] == ["no_bench_span", pytest.approx(30e-9)]
    assert dt.idle_gaps[1] == ["bench.call", pytest.approx(10e-9)]
    assert dt.idle_gaps[2] == ["bench.call", pytest.approx(10e-9)]


def test_innermost_span_names_the_host_work():
    spans = _timeline().spans
    assert trace.innermost_span(spans, 63) == "bench.fetch"
    assert trace.innermost_span(spans, 66) == trace.NO_SPAN


def test_readers_on_the_reduction():
    tl = _timeline()
    ctx = {"device": trace.reduce(tl),
           "counters": {"trips": 10, "lane_steps": 30, "lane_trips": 40,
                        "calls": 2, "call_s": 3.0, "launch_s": 2.5}}
    assert run.reader("lane_occupancy.batch")(ctx) == pytest.approx(75.0)
    # 2.5 s of launches over 10 trips; 0.5 s of host time over 2 calls
    assert run.reader("trip_us.batch")(ctx) == pytest.approx(250000.0)
    assert run.reader("host_ms_per_call.batch")(ctx) == pytest.approx(250.0)
    assert run.reader("trip_us.batch")({"counters": {"calls": 1}}) is None
    assert run.reader("host_ms_per_call.batch")({"counters": {}}) is None
    assert run.reader("lane_occupancy.serve")(ctx) is None
    assert run.reader("queue_wait_ms.serve")(
        {"counters": {"requests": 4, "wait_sum_s": 0.2}}) == \
        pytest.approx(50.0)


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(trace.Timeline({}, [("bench.window", 0, 1)]))


def test_recorded_tpu_trace():
    """A window recorded on a TPU v5 lite (the first 400 operations of an
    8-lane ``run_many`` call inside ``bench.call``), reduced as a run
    reduces it."""
    rec = json.loads((DATA / "tpu_window.json").read_text())
    tl = trace.Timeline({k: [tuple(e) for e in v]
                         for k, v in rec["ops"].items()},
                        [tuple(s) for s in rec["spans"]],
                        {k: [tuple(e) for e in v]
                         for k, v in rec["modules"].items()})
    dt = trace.reduce(tl)
    want = rec["expect"]
    assert dt.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert dt.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert [n for n, _ in dt.idle_gaps] == want["idle_gap_spans"]
    assert 0 < dt.busy_s <= dt.window_s


def test_a_trace_that_dropped_events_is_read_where_it_ends():
    """The profiler ran out of room and dropped events (as it does a few
    seconds into a machine run): the window is cut where the kept device
    events end, and busy time and gaps are read over that stretch."""
    def timeline(dropped):
        return trace.Timeline(
            {"/device:TPU:0": [("fusion.1", 10, 25), ("while", 25, 40)]},
            [("bench.window", 0, 100), ("bench.call", 5, 45),
             ("bench.call", 50, 95)],
            {"/device:TPU:0": [("jit_run_population", 10, 40)]}, dropped)
    full = trace.reduce(timeline(0))
    assert not full.cut and full.window_s == pytest.approx(100e-9)
    cut = trace.reduce(timeline(515394))
    assert cut.cut
    assert cut.window_s == pytest.approx(40e-9)
    assert cut.busy_s == pytest.approx(30e-9)
    assert cut.idle_gaps == [["bench.call", pytest.approx(10e-9)]]
