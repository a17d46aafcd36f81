"""A pool of generated scenarios and a stream of requests over it.

The pool and the due times come from the traffic file's ``library_seed``
(the due times from the process its ``arrivals`` entry names), and every
pool scenario is requested equally often; the run's seed sets only the
order in which the requests name them (and the check's sample).
"""
import numpy as np

from hts_bench import gen


def generate(seed: int, t: dict, seconds: float) -> gen.Traffic:
    lib = t["library_seed"]
    progs = [gen.scenario([lib, 0, k], kernels=t["kernels"],
                          max_tasks=t["max_tasks"],
                          n_tenants=t.get("n_tenants"),
                          name=f"p{k}").build() for k in range(t["pool"])]
    due = gen.due_times(np.random.default_rng([lib, 1]), t["arrivals"],
                        seconds)
    pick = np.random.default_rng([seed, 2]).permutation(
        np.arange(len(due)) % len(progs))
    return gen.Traffic(progs, arrivals=due, requests=pick)
