"""Find the highest rate a serving cell sustains: run its open-loop window
at each offered rate and seed, in one process on one warmed server, and
print one JSON line per window.

    python3 hts_bench/sweep_rate.py --workload serve.generated_open \
        --seeds 1 2 --seconds 51 --rates 20 30 40

A rate is sustained when the backlog (requests due but not finished)
stops growing: its least-squares growth over the window's second half
(``backlog_growth_per_s`` in the notes) stays near 0 rather than near the
offered rate less the served one.  The cell's traffic file then fixes its
rate at about four fifths of the highest sustained one; the benchmark's
own runs never search for a rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from hts_bench import common, gen, run
    spec = run.load_cell(args.workload)
    run.device_info(spec["cell"]["chips"])
    run.enable_compile_cache()
    base = spec["traffic"]
    drv = run.driver_class(base)(spec["config"], base, args.seeds[0],
                                 args.seconds, common.Spans())
    drv.setup()                  # the pool, and so the warm-up, is the same
    for rate in args.rates:      # at every rate and seed
        t = dict(base, arrivals=dict(base["arrivals"], rate_per_s=rate))
        for seed in args.seeds:
            drv.traffic = gen.generate(seed, t, args.seconds)
            drv.served, drv.unanswered = {}, 0
            stats = drv.window(args.seconds)
            print(json.dumps({"rate_per_s": rate, "seed": seed,
                              "attempted": stats["attempted"],
                              "failed": stats["failed"], **stats["metrics"],
                              "notes": stats["notes"]}), flush=True)
    drv.release()


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    main()
