"""The reduction of ``hts_bench/breakdown.py``: self time of nested
operations, step phases from ``op_name`` paths and the HLO a profile
stores, idle time by innermost span, and the server's report."""
import dataclasses

import pytest

from hts_bench import breakdown as tb
from hts_bench import trace


def test_scope_of_strips_transforms_and_nests():
    assert tb.scope_of("jit(run_population)/while/body/vmap(step_bottom)/"
                       "next_dt/min") == "step_bottom/next_dt"
    assert tb.scope_of("jit(run_population)/while/body/vmap(rs_issue)/"
                       "trace_write/jit(_where)") == "rs_issue/trace_write"
    assert tb.scope_of("jit(run_population)/while/cond/lt") == "unscoped"
    assert tb.scope_of("") == "unscoped"


def test_hlo_op_names_inherit_the_calling_loop():
    """An instruction without metadata (a scatter the compiler expanded
    into a loop) takes the op_name of the loop that runs it."""
    P = tb._protos()
    hlo = P["HloProto"]()
    m = hlo.hlo_module
    body = m.computations.add(name="body", id=1)
    body.instructions.add(name="p")
    body.instructions.add(name="dus.1")
    cond = m.computations.add(name="cond", id=2)
    cond.instructions.add(name="lt").metadata.op_name = "jit(f)/while/cond/lt"
    main = m.computations.add(name="main", id=3)
    main.instructions.add(name="x")
    loop = main.instructions.add(name="while.7")
    loop.metadata.op_name = "jit(f)/while/body/vmap(fu_exec)/scatter"
    loop.called_computation_ids.extend([2, 1])
    main.instructions.add(name="out")
    again = P["HloProto"]()
    again.ParseFromString(hlo.SerializeToString())   # as a profile holds it
    names = tb.hlo_op_names(again.hlo_module)
    assert tb.scope_of(names["dus.1"]) == "fu_exec"
    assert names["lt"] == "jit(f)/while/cond/lt"
    assert names["x"] == "" and names["out"] == ""


def test_profile_hlo_reads_a_cpu_profile(tmp_path):
    """The optimized program a CPU profile ran, with a scope's name in
    its instructions' op_name."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        def body(c):
            i, x = c
            with jax.named_scope("fu_exec"):
                x = x.at[i % 4].add(1.0)
            return i + 1, x
        return jax.lax.while_loop(lambda c: c[0] < 8, body, (0, x))[1]

    x = jnp.zeros(4)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    progs = tb.profile_hlo(str(path))
    (names,) = [v for k, v in progs.items() if k.startswith("jit_f")]
    assert "fu_exec" in {tb.scope_of(v) for v in names.values()}


def test_self_times_count_a_loop_body_once():
    """A while op holding two ops keeps only its own time; clipping to the
    kept stretch cuts the loop where its recorded body ends."""
    evs = [("while", 0, 100), ("a", 10, 30), ("b", 40, 90),
           ("c", 120, 130)]
    got = dict(tb.self_times(evs, 0, 200))
    assert got == {"while": 30, "a": 20, "b": 50, "c": 10}
    assert sum(got.values()) == 110           # the busy time, not 180
    clipped = dict(tb.self_times(evs, 20, 60))
    assert clipped == {"while": 10, "a": 10, "b": 20}


def test_phases_shares():
    ops = {"/device:TPU:0": [("while", 0, 100), ("f1", 10, 30),
                             ("f2", 40, 90), ("cond", 100, 110)]}
    scopes = {"while": "jit(run_population)/while",
              "f1": "jit(run_population)/while/body/vmap(cdb_enqueue)/"
                    "trace_write/scatter",
              "f2": "jit(run_population)/while/body/vmap(rs_issue)/add"}
    out = tb.phases(ops, scopes, 0, 110)
    assert dict(out["step_phases"]) == pytest.approx(
        {"cdb_enqueue/trace_write": 20e-9, "rs_issue": 50e-9,
         "unscoped": 40e-9})
    assert out["trace_write_share_pct"] == pytest.approx(100 * 20 / 110)
    assert out["unscoped_pct"] == pytest.approx(100 * 40 / 110)
    assert [n for n, _, _ in out["top_unscoped"]] == ["while", "cond"]


def test_idle_by_span_splits_gaps_at_span_edges():
    """Device busy [0, 10) and [50, 60) of a [0, 100) window: the idle
    stretch [10, 50) lies half in no span and half in a launch's pack;
    [60, 100) is in the launch alone."""
    merged = {"/device:TPU:0": trace.union([(0, 10), (50, 60)], 0, 100)}
    spans = [(trace.WINDOW_SPAN, 0, 100), ("hts.serve.launch", 30, 100),
             ("hts.serve.pack", 30, 50)]
    got = tb.idle_by_span(merged, spans, 0, 100)
    assert got == pytest.approx({"none": 20e-9, "hts.serve.pack": 20e-9,
                                 "hts.serve.launch": 40e-9})


def test_serve_report_adds_up_to_submit_to_result():
    """On a manual clock, the breakdown's means of the server's report:
    queue wait plus time in a lane, and that plus the submit hold, is
    each request's time from ``submit()`` to its result."""
    from repro.core import hts
    from repro.core.hts.builder import Program

    def tiny(name):
        p = Program(name, region_base=0x100)
        p.task("vector_dot", in_=p.input(0x10, 4, "frame"), out=4,
               in_size=4, tid=0)
        return p

    tick = 2.0 ** -6
    clock = hts.ManualClock()
    srv = hts.serve(max_batch=2, deadline=3 * tick, slice_steps=4096,
                    clock=clock)
    real = srv._runner

    def runner(key):                   # each slice takes one tick
        rm = real(key)

        def run_slice(*args):
            clock.advance(tick)
            return rm.run_slice(*args)
        return dataclasses.replace(rm, run_slice=run_slice)
    srv._runner = runner
    futs = [srv.submit(tiny("a")), srv.submit(tiny("b"))]
    clock.advance(4 * tick)
    futs.append(srv.submit(tiny("c")))  # its deadline flush runs a and b
    srv.drain()
    assert all(f.result(timeout=0).halted for f in futs)
    out = tb.serve_report(srv.report())
    # a, b: 4 ticks queued, 1 in a lane; c: held 1, queued 0, in a lane 1
    assert out["queue_wait_mean"] == pytest.approx(8 / 3 * tick)
    assert out["wait_plus_lane_mean"] == pytest.approx(11 / 3 * tick)
    assert out["submit_to_result_mean"] == pytest.approx(4 * tick)
    assert out["launches_by_reason"]["deadline"] == 1
    assert set(tb.REPORT_FIELDS) <= set(out)
