"""The control of the comparison: the reference with one stated guarantee
broken, put in the program's place.

The configuration names the guarantee under its ``"control"`` key:

* ``"speculation"`` (the default where the key is absent): ``hts_spec``
  speculates on memory- and bus-read branches (predict not taken, squash
  on a misprediction).  The control runs the reference with speculation
  off, the step a later change that drops the speculation bookkeeping
  would take.
* ``"policy"``: each answer obeys its scheduling policy (priority weights,
  FU quotas, RS caps).  The control runs the reference with the policy
  dropped, which is what a change that skipped the arbiter's policy
  tables would produce.

For each seed this builds the cell's traffic at the cell's own size, draws
the same number of answers as a run's check, and counts those on which the
control disagrees with the reference: the number the check would report
had the control produced the answers.  The benchmark's own runs never run
this.

    python3 hts_bench/control.py --workload <cell> --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _answers(spec: dict, seed: int, seconds: float) -> list:
    """(built program, scheduler, n_fu, policy) of every answer one run
    would compare against, in a form the reference takes."""
    from hts_bench import gen
    cfg, t = spec["config"], spec["traffic"]
    traffic = gen.generate(seed, t, seconds)
    pols = traffic.policies or [None] * len(traffic.programs)
    if t["driver"] == "serve_open":
        return [(traffic.programs[k], cfg["scheduler"], cfg["n_fu"], pols[k])
                for k in traffic.requests]
    if t["entry"] == "sweep":
        return [(p, s, f, pol) for s in t["schedulers"]
                for p, pol in zip(traffic.programs, pols) for f in t["n_fu"]]
    return [(p, cfg["scheduler"], cfg["n_fu"], pol)
            for p, pol in zip(traffic.programs, pols)]


def _no_speculation(cfg, built, sched, n_fu, policy):
    from hts_bench import common
    return common.expect(built, cfg, sched, n_fu, policy=policy, sched=dict(
        cfg["schedulers"][sched], speculation=False))


def _no_policy(cfg, built, sched, n_fu, policy):
    from hts_bench import common
    return common.expect(built, cfg, sched, n_fu)


#: the configuration's ``"control"`` -> the reference with that broken
BROKEN = {"speculation": _no_speculation, "policy": _no_policy}


def reading(spec: dict, seed: int, seconds: float = 30.0) -> dict:
    from hts_bench import common
    cfg, t = spec["config"], spec["traffic"]
    guarantee = cfg.get("control", "speculation")
    if guarantee not in BROKEN:
        raise SystemExit(f"hts_bench: no control breaks {guarantee!r} "
                         f"(have {sorted(BROKEN)})")
    answers = _answers(spec, seed, seconds)
    k = t.get("check_lanes") or t.get("check_points") or t["check_requests"]
    pick = common.sample(np.random.default_rng([seed, 7]), len(answers), k)
    bad = 0
    for j in pick:
        built, sched, n_fu, policy = answers[j]
        want = common.expect(built, cfg, sched, n_fu, policy=policy)
        got = BROKEN[guarantee](cfg, built, sched, n_fu, policy)
        bad += common.differs(got.cycles, got.halted,
                              None if t.get("entry") == "sweep" else got.rows,
                              want)
    return {"seed": seed, "compared": len(pick), "differing": bad}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from hts_bench import run
    spec = run.load_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps(dict(reading(spec, seed), workload=args.workload)),
              flush=True)


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    main()
