"""Run one cell of the HTS benchmark on the chip.

    python3 hts_bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``hts_bench/configs/<config>.json``, its traffic mix
``hts_bench/traffic/<traffic>.json`` (whose ``driver`` key names a module
of ``hts_bench/drivers/``, and whose ``generator`` and ``arrivals.kind``
name modules of ``hts_bench/generators/`` and ``hts_bench/arrivals/``),
and each per-layer metric is read by ``hts_bench/metrics/<metric>.py``: a
cell, configuration, mix, generator kind or metric is added by adding
files and entries, never by editing this one.

A run refuses to start unless JAX's first device is a TPU and it sees the
chips the cell asks for.  It builds the cell's traffic from ``--seed``,
warms up every shape that traffic uses (set-up), measures for
``--seconds``, reads the peak device memory, frees the program's state and
compares a seeded sample of the window's answers with the plain reference
(``hts_bench/reference.py``).  With ``--trace 1`` the window is shorter
(the traffic file's ``trace_seconds``) and profiled, and the run reports
the cell's per-layer metrics instead of its end-to-end ones.  The last
line of standard output is one JSON object; the numbers compared, each
with its limit, end standard error.
"""
import time

_T0 = time.monotonic()      # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed path in the checkout,
#: unless the environment names one
CACHE_DIR = ROOT / ".jax_cache"
#: lowering and XLA compilation events of ``jax.monitoring``
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends lowering and compiling, and the number of
    backend compiles, from ``jax.monitoring`` duration events (as
    ``chip_smoke.py`` counts them)."""

    def __init__(self):
        self.secs = 0.0
        self.compiles = 0

    def __call__(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.secs += duration
        if event == _COMPILE_EVENTS[-1]:
            self.compiles += 1


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic and metric entries."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"hts_bench: no workload {name!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[name]
    here = root / "hts_bench"

    def listed(m):
        return name in m.get("workloads", [name])

    return {
        "cell": cell,
        "config": json.loads(
            (here / "configs" / f"{cell['config']}.json").read_text()),
        "traffic": json.loads(
            (here / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
        "per_layer": [m for m in bench["per_layer"] if listed(m)],
    }


def reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read`` function of ``hts_bench/metrics/<metric>.py``."""
    path = root / "hts_bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"hts_bench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver_class(traffic: dict):
    return importlib.import_module(
        f"hts_bench.drivers.{traffic['driver']}").Driver


def device_info(chips: int) -> dict:
    """Refuse to run without a TPU and the chips the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"hts_bench: no TPU: JAX's first device is "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"hts_bench: the cell needs {chips} chips, JAX "
                         f"sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def enable_compile_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run(args) -> dict:
    """One run; returns the result line's object (``checks`` last)."""
    spec = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("hts_bench: the system under test (src/repro) is "
                         "not in this checkout")
    sys.path[:0] = [str(ROOT / "src")]
    import jax

    from hts_bench import common, trace

    chips = spec["cell"]["chips"]
    device = device_info(chips)
    enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    traffic = spec["traffic"]
    seconds = (min(args.seconds, traffic["trace_seconds"]) if args.trace
               else args.seconds)
    spans = common.Spans()
    drv = driver_class(traffic)(spec["config"], traffic, args.seed, seconds,
                                spans)
    drv.setup()
    setup_s = time.monotonic() - _T0
    c_setup, n_setup = clock.secs, clock.compiles
    print(f"setup: setup_s={setup_s!r} compile_s={c_setup!r} "
          f"compiles={n_setup}", flush=True)

    if args.trace:
        with trace.capture() as box:
            with spans(trace.WINDOW_SPAN):
                stats = drv.window(seconds)
    else:
        stats = drv.window(seconds)
    print(f"window: compile_s={clock.secs - c_setup!r} "
          f"compiles={clock.compiles - n_setup} (expected 0)", flush=True)
    for line in stats.get("notes", ()):
        print(line, flush=True)

    device["memory_peak_bytes"] = memory_peak(chips)
    counters = drv.counters() if args.trace else {}
    drv.release()
    gc.collect()
    t_check = time.monotonic()
    checks = drv.check()
    print(f"check: reference_s={time.monotonic() - t_check!r}", flush=True)

    metrics = {}
    out = {"correct": all(c.ok for c in checks),
           "attempted": stats["attempted"], "failed": stats["failed"],
           "metrics": metrics, "device": device}
    if args.trace:
        t_trace = time.monotonic()
        dt = trace.reduce(box[0])
        print(f"trace: reduce_s={time.monotonic() - t_trace!r} "
              f"dropped_events={box[0].dropped} window_s={dt.window_s!r} "
              f"(cut where the kept events end: {dt.cut})", flush=True)
        device["busy_s"] = dt.busy_s
        device["window_s"] = dt.window_s
        ctx = {"counters": counters, "device": dt}
        for m in spec["per_layer"]:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": dt.top_ops,
                            "idle_gaps": dt.idle_gaps}
    else:
        values = dict(stats["metrics"], setup_s=setup_s)
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(args)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT)]
    main()
