"""Closed-loop populations of generated multi-tenant scenarios.

``populations`` populations of ``lanes`` scenarios each, drawn from the
traffic file's ``library_seed``; the run's calls cycle through them in
order.  Every run does the same work: the run's seed sets only the order
of the lanes within each call (and the check's sample).
"""
import numpy as np

from hts_bench import gen


def generate(seed: int, t: dict, seconds: float) -> gen.Traffic:
    progs, calls = [], []
    lib = t["library_seed"]
    for i in range(t["populations"]):
        idx = []
        for k in range(t["lanes"]):
            idx.append(len(progs))
            progs.append(gen.scenario([lib, i, k], kernels=t["kernels"],
                                      max_tasks=t["max_tasks"],
                                      n_tenants=t.get("n_tenants"),
                                      name=f"s{i}_{k}").build())
        calls.append([int(x) for x in
                      np.random.default_rng([seed, i]).permutation(idx)])
    return gen.Traffic(progs, calls)
