"""Name where a traced benchmark window spends its time, on the chip.

    python3 hts_bench/breakdown.py --workload <cell> --seed <n> \
        [--seconds 8] [--out breakdown_out]
    python3 hts_bench/breakdown.py --span-cost

The first form runs one cell of ``BENCHMARK.json`` the way ``run.py
--trace 1`` does (the cell's driver, set-up outside the trace, a profiled
window) and reads the profile three ways:

* the harness's own reduction (``trace.reduce``): busy time, the kept
  stretch, top operations and idle gaps by ``bench.*`` span;
* ``step_phases``: device self time over the kept stretch by the step
  phase named in each operation's ``jax.named_scope`` path (``unscoped``
  for the rest), and the share under a ``trace_write`` scope;
* ``idle_by_span``: the kept stretch's idle time split by the innermost
  ``bench.*`` or ``hts.*`` span open at each moment (``none``: no span).

For a serving cell it adds the server's ``report(since=)`` over the
window, beside the client's own timing of the same requests.  The
``--span-cost`` form times one ``Spans`` call with no profiler running
and with one running.  Results go to ``<out>/<name>.json``.

It reads what the program's spans and scopes give and the harness does
not read yet; ``trace.py``, ``run.py`` and the serving driver would take
its pieces to report them as per-layer metrics.
"""
from __future__ import annotations

import argparse
import bisect
import functools
import glob
import json
import os
import pathlib
import re
import statistics
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent

#: the scopes ``machine.py`` puts on the step's phases and trace writes
PHASES = ("step_top", "fu_exec", "cdb_enqueue", "memread_tick", "cdb_grant",
          "branch_core", "abort_traces", "rs_issue", "frontend_core",
          "dispatch_traces", "step_bottom", "next_dt", "trace_write")
_WRAPPER = re.compile(r"^(?:vmap|jit|pjit|jvp|transpose|remat)\((.*)\)$")


def scope_of(op_name: str) -> str:
    """The step phases on an ``op_name`` path, outermost first, joined by
    ``/`` (``unscoped`` if none): ``.../vmap(step_bottom)/next_dt/min``
    gives ``step_bottom/next_dt``."""
    parts = []
    for part in op_name.split("/"):
        m = _WRAPPER.match(part)
        part = m.group(1) if m else part
        if part in PHASES and part not in parts:
            parts.append(part)
    return "/".join(parts) or "unscoped"


def self_times(events, lo: float, hi: float) -> list:
    """``[(name, self ns)]`` of ``[(name, start, end)]`` operations on one
    device line, clipped to [lo, hi]: an operation's time less that of
    the operations nested in it, so a while loop does not count its body
    twice."""
    evs = sorted(((n, max(s, lo), min(e, hi)) for n, s, e in events
                  if min(e, hi) > max(s, lo)),
                 key=lambda x: (x[1], -x[2]))
    out = []
    stack: list = []                  # [index, end, nested ns]
    for i, (_, s, e) in enumerate(evs):
        while stack and stack[-1][1] <= s:
            j, _, inner = stack.pop()
            out.append((evs[j][0], evs[j][2] - evs[j][1] - inner))
        if stack:
            stack[-1][2] += min(e, stack[-1][1]) - s
        stack.append([i, e, 0])
    for j, _, inner in stack:
        out.append((evs[j][0], evs[j][2] - evs[j][1] - inner))
    return out


def phases(ops: dict, scopes: dict, lo: float, hi: float) -> dict:
    """Device self time (s) by step phase over [lo, hi], summed over the
    devices; ``scopes`` maps an operation's name to its ``op_name``."""
    by: dict = {}
    per_op: dict = {}
    for evs in ops.values():
        for name, ns in self_times(evs, lo, hi):
            key = scope_of(scopes.get(name, ""))
            by[key] = by.get(key, 0.0) + ns * 1e-9
            per_op[name] = per_op.get(name, 0.0) + ns * 1e-9
    unscoped = {n: v for n, v in per_op.items()
                if scope_of(scopes.get(n, "")) == "unscoped"}
    total = sum(by.values())
    tw = sum(v for k, v in by.items() if "trace_write" in k.split("/"))
    return {"step_phases": sorted(([k, v] for k, v in by.items()),
                                  key=lambda kv: -kv[1]),
            "self_s": total,
            "trace_write_share_pct": 100.0 * tw / total if total else None,
            "unscoped_pct": 100.0 * by.get("unscoped", 0.0) / total
            if total else None,
            "top_unscoped": [[n, v, scopes.get(n, "")] for n, v in sorted(
                unscoped.items(), key=lambda kv: -kv[1])[:15]],
            "self_by_op": [[n, v, scopes.get(n, "")] for n, v in sorted(
                per_op.items(), key=lambda kv: -kv[1])[:300]]}


def innermost(spans_sorted, starts, longest: float, t: float) -> str:
    """The shortest span open at ``t`` (``none`` if none)."""
    best = None
    k = bisect.bisect_right(starts, t)
    i = bisect.bisect_left(starts, t - longest)
    for name, s, e in spans_sorted[i:k]:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "none"


def idle_by_span(merged: dict, spans: list, lo: float, hi: float) -> dict:
    """Idle seconds of the devices over [lo, hi] (averaged over devices),
    split at span boundaries by the innermost span open."""
    from hts_bench import trace
    spans = sorted((s for s in spans if s[0] != trace.WINDOW_SPAN),
                   key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    longest = max((e - s for _, s, e in spans), default=0)
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    out: dict = {}
    for m in merged.values():
        for g0, g1 in trace.gaps(m, lo, hi):
            cuts = [g0] + edges[bisect.bisect_right(edges, g0):
                                bisect.bisect_left(edges, g1)] + [g1]
            for a, b in zip(cuts, cuts[1:]):
                name = innermost(spans, starts, longest, (a + b) / 2)
                out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return {k: v / max(len(merged), 1) for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])}


@functools.cache
def _protos() -> dict:
    """Message classes for the few fields read here of the profile's
    ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``: each plane's event
    and statistic metadata; its events are skipped) and of ``xla.HloProto``
    (``xla/service/hlo.proto``: each instruction's name, ``op_name`` and
    the computations it calls), so a profile is read without the
    profiler's or XLA's own Python modules."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    T = descriptor_pb2.FieldDescriptorProto
    i64, s, b, msg = T.TYPE_INT64, T.TYPE_STRING, T.TYPE_BYTES, \
        T.TYPE_MESSAGE
    messages = {   # name: [(field, number, type, repeated, message type)]
        "XStat": [("metadata_id", 1, i64, False, None),
                  ("double_value", 2, T.TYPE_DOUBLE, False, None),
                  ("uint64_value", 3, T.TYPE_UINT64, False, None),
                  ("int64_value", 4, i64, False, None),
                  ("str_value", 5, s, False, None),
                  ("bytes_value", 6, b, False, None),
                  ("ref_value", 7, T.TYPE_UINT64, False, None)],
        "XEventMetadata": [("id", 1, i64, False, None),
                           ("name", 2, s, False, None),
                           ("stats", 5, msg, True, "XStat")],
        "XStatMetadata": [("id", 1, i64, False, None),
                          ("name", 2, s, False, None)],
        # map<int64, ...> entries, read as repeated (key, value) pairs
        "EventMetadataEntry": [("key", 1, i64, False, None),
                               ("value", 2, msg, False, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, i64, False, None),
                              ("value", 2, msg, False, "XStatMetadata")],
        "XPlane": [("name", 2, s, False, None),
                   ("event_metadata", 4, msg, True, "EventMetadataEntry"),
                   ("stat_metadata", 5, msg, True, "StatMetadataEntry")],
        "XSpace": [("planes", 1, msg, True, "XPlane")],
        "OpMetadata": [("op_name", 2, s, False, None)],
        "HloInstructionProto": [
            ("name", 1, s, False, None),
            ("metadata", 7, msg, False, "OpMetadata"),
            ("called_computation_ids", 38, i64, True, None)],
        "HloComputationProto": [
            ("name", 1, s, False, None),
            ("instructions", 2, msg, True, "HloInstructionProto"),
            ("id", 5, i64, False, None)],
        "HloModuleProto": [
            ("name", 1, s, False, None),
            ("computations", 3, msg, True, "HloComputationProto")],
        "HloProto": [("hlo_module", 1, msg, False, "HloModuleProto")]}
    f = descriptor_pb2.FileDescriptorProto(name="hts_profile.proto",
                                           package="hts", syntax="proto3")
    for mname, fields in messages.items():
        m = f.message_type.add(name=mname)
        for fname, num, typ, rep, tname in fields:
            fd = m.field.add(name=fname, number=num, type=typ,
                             label=T.LABEL_REPEATED if rep
                             else T.LABEL_OPTIONAL)
            if tname:
                fd.type_name = f".hts.{tname}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"hts.{name}")) for name in messages}


def hlo_op_names(module) -> dict:
    """Instruction name -> ``op_name`` of one ``HloModuleProto``.  An
    instruction the compiler made without one (a scatter or gather it
    expanded into a loop, say) takes that of the instruction whose loop,
    fusion or call runs its computation."""
    own: dict = {}
    comp_of: dict = {}
    caller: dict = {}               # computation id -> calling instruction
    for comp in module.computations:
        for ins in comp.instructions:
            own[ins.name] = ins.metadata.op_name
            comp_of[ins.name] = comp.id
            for cid in ins.called_computation_ids:
                caller.setdefault(cid, ins.name)
    out: dict = {}

    def resolve(name, depth=0):
        if name not in out:
            parent = caller.get(comp_of.get(name))
            out[name] = own.get(name) or (
                resolve(parent, depth + 1) if parent and depth < 64 else "")
        return out[name]

    for name in own:
        resolve(name)
    return out


def profile_hlo(path: str) -> dict:
    """Instruction name -> ``op_name`` of every optimized program a
    profile ran, by program, from the ``Hlo Proto`` statistic the profiler
    stores in its ``/host:metadata`` plane."""
    P = _protos()
    space = P["XSpace"]()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out: dict = {}
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if names.get(st.metadata_id) in ("Hlo Proto", "hlo_proto"):
                    hlo = P["HloProto"]()
                    hlo.ParseFromString(st.bytes_value)
                    out[entry.value.name or hlo.hlo_module.name] = \
                        hlo_op_names(hlo.hlo_module)
    return out


def read_profile(path: str) -> dict:
    """Device operations, programs and host spans of one ``.xplane.pb``,
    with each operation's ``op_name`` from the HLO of the programs the
    profile ran; where names repeat across programs, the largest
    program's wins."""
    from jax.profiler import ProfileData
    from hts_bench import trace
    ops: dict = {}
    modules: dict = {}
    spans: list = []
    dropped = 0
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        if device:
            dropped += sum(int(v) for k, v in (plane.stats or ())
                           if k == trace.DROPPED_STAT)
        for line in plane.lines:
            if device and line.name == trace.OPS_LINE:
                evs = [(e.name.split(" ")[0], e.start_ns,
                        e.start_ns + e.duration_ns) for e in line.events]
                if evs:
                    ops[plane.name] = evs
            elif device and line.name == trace.MODULES_LINE:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if evs:
                    modules[plane.name] = evs
            elif not device:
                spans += [(e.name.split("#")[0], e.start_ns,
                           e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(("bench.", "hts."))]
    programs = profile_hlo(path)
    names: dict = {}
    for prog in sorted(programs.values(), key=len):   # the largest wins
        names.update(prog)
    seen = {n for evs in ops.values() for n, _, _ in evs}
    scopes = {n: names.get(n.lstrip("%"), "") for n in seen}
    return {"ops": ops, "modules": modules, "spans": spans,
            "scopes": scopes, "dropped": dropped,
            "programs": sorted(programs)}


def breakdown(path: str) -> dict:
    from hts_bench import trace
    p = read_profile(path)
    tl = trace.Timeline(p["ops"], [s for s in p["spans"]
                                   if s[0].startswith(trace.SPAN_PREFIX)],
                        p["modules"], p["dropped"])
    dt = trace.reduce(tl)
    lo, hi = tl.window()
    hi = lo + dt.window_s * 1e9
    devices = sorted(set(p["ops"]) | set(p["modules"]))
    merged = {d: trace.union([(s, e) for _, s, e in p["ops"].get(d, [])
                              + p["modules"].get(d, [])], lo, hi)
              for d in devices}
    merged = {d: m for d, m in merged.items() if m}
    span_count: dict = {}
    for n, _, _ in p["spans"]:
        span_count[n] = span_count.get(n, 0) + 1
    return {"harness": {"busy_s": dt.busy_s, "window_s": dt.window_s,
                        "cut": dt.cut, "dropped": p["dropped"],
                        "top_ops": dt.top_ops, "idle_gaps": dt.idle_gaps},
            "scoped_ops": sum(bool(v) for v in p["scopes"].values()),
            "programs": p["programs"],
            "distinct_ops": len(p["scopes"]),
            "span_count": span_count,
            "idle_by_span": idle_by_span(merged, p["spans"], lo, hi),
            **phases(p["ops"], p["scopes"], lo, hi)}


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------
def _client_timing(srv) -> list:
    """Each request's seconds from the client's call of ``srv.submit`` to
    its result, on the client's clock, in the returned list.  The serving
    driver keeps its own per-request times inside its window, so they are
    taken here at the same call."""
    took: list = []
    submit = srv.submit

    def timed(program, **kw):
        t0 = time.monotonic()
        fut = submit(program, **kw)
        fut.add_done_callback(
            lambda _, t0=t0: took.append(time.monotonic() - t0))
        return fut
    srv.submit = timed
    return took


#: the fields of ``ServeReport`` a serving breakdown keeps
REPORT_FIELDS = (
    "requests", "batches", "submit_hold_mean", "submit_hold_max",
    "queue_wait_p50", "queue_wait_p95", "in_lane_mean", "in_lane_p50",
    "in_lane_p95", "slices_p50", "slices_p95", "launch_delay_mean",
    "launch_delay_max", "launch_wall_mean", "launch_wall_max",
    "launch_queue_mean", "launch_queue_max", "launch_slices_mean",
    "launch_slices_max", "host_share", "phase_s", "launches_by_reason")


def serve_report(rep) -> dict:
    """A ``ServeReport`` as the breakdown gives it, with the mean of queue
    wait plus time in a lane (``wait_plus_lane_mean``, the server's time
    from enqueueing a request to its result) and that plus the time
    ``submit()`` held the caller (``submit_to_result_mean``)."""
    out = {k: getattr(rep, k) for k in REPORT_FIELDS}
    n = sum(b.requests for b in rep.per_bucket.values())
    wait = sum(b.mean_wait * b.requests
               for b in rep.per_bucket.values()) / n if n else float("nan")
    out["queue_wait_mean"] = wait
    out["wait_plus_lane_mean"] = wait + rep.in_lane_mean
    out["submit_to_result_mean"] = (out["wait_plus_lane_mean"]
                                    + rep.submit_hold_mean)
    return out


def traced_cell(args) -> dict:
    import jax
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from hts_bench import common, trace
    from hts_bench import run as bench
    spec = bench.load_cell(args.workload)
    device = bench.device_info(spec["cell"]["chips"])
    # compile in this process: JAX's persistent cache keys a program
    # without its op metadata, so it may hand back an executable compiled
    # before the step phases had scopes, whose ops then name no phase
    jax.config.update("jax_enable_compilation_cache", False)
    traffic = spec["traffic"]
    seconds = min(args.seconds, traffic["trace_seconds"])
    spans = common.Spans()
    drv = bench.driver_class(traffic)(spec["config"], traffic, args.seed,
                                      seconds, spans)
    t0 = time.monotonic()
    drv.setup()
    out = {"workload": args.workload, "seed": args.seed, "device": device,
           "setup_s": time.monotonic() - t0}
    serving = hasattr(drv, "srv")
    took = _client_timing(drv.srv) if serving else None
    with tempfile.TemporaryDirectory(prefix="hts_breakdown_") as d:
        since = time.monotonic()
        jax.profiler.start_trace(d, profiler_options=trace.options())
        try:
            with spans(trace.WINDOW_SPAN):
                stats = drv.window(seconds)
        finally:
            jax.profiler.stop_trace()
        out["write_s"] = time.monotonic() - since - seconds
        out["notes"] = stats.get("notes", [])
        if serving:
            out["report"] = serve_report(drv.srv.report(since=since))
            out["client_submit_to_result_mean"] = statistics.fmean(took)
            out["client_requests"] = len(took)
        else:
            out["counters"] = drv.counters()
        (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True)
        out["xplane_bytes"] = os.path.getsize(path)
        t1 = time.monotonic()
        out.update(breakdown(path))
        out["reduce_s"] = time.monotonic() - t1
    drv.release()
    return out


def span_cost(n: int = 200_000) -> dict:
    """Microseconds per ``Spans`` call (with arguments, as a launch's) and
    per bare ``TraceAnnotation``, with no profiler and with one running."""
    import jax
    from jax.profiler import TraceAnnotation
    sys.path[:0] = [str(ROOT / "src")]
    from hts_bench import trace
    from repro.core.hts.spans import Spans

    def loop():
        spans = Spans()
        t = time.perf_counter()
        for _ in range(n):
            with spans("hts.serve.launch", bucket="(16, 1)", lanes=8,
                       queue=9, reason="deadline"):
                pass
        helper = (time.perf_counter() - t) / n * 1e6
        t = time.perf_counter()
        for _ in range(n):
            with TraceAnnotation("hts.serve.slice"):
                pass
        return helper, (time.perf_counter() - t) / n * 1e6

    out = {"device": jax.devices()[0].device_kind, "calls": n}
    out["off_helper_us"], out["off_annotation_us"] = loop()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=trace.options())
        try:
            out["on_helper_us"], out["on_annotation_us"] = loop()
        finally:
            jax.profiler.stop_trace()
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=2147489001)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--span-cost", action="store_true")
    ap.add_argument("--out", default=str(ROOT / "breakdown_out"))
    args = ap.parse_args(argv)
    if args.span_cost:
        out, name = span_cost(), "span_cost"
    elif args.workload:
        out, name = traced_cell(args), f"{args.workload}.{args.seed}"
    else:
        ap.error("give --workload or --span-cost")
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{name}.json"), "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(json.dumps({k: v for k, v in out.items() if k not in (
        "top_unscoped", "self_by_op")},
        default=str)[:6000], flush=True)


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT)]
    main()
