"""The QoS policy grid, closed loop: one call of every instance.

``gen.qos_grid`` over the traffic file's ``mixes``, ``weights``,
``quotas`` and ``rs_caps`` (``null`` or 0: not set), each instance one
lane under its own policy.  Every run does the same work: the run's seed
sets only the order of the lanes in the call (and the check's sample).
"""
import numpy as np

from hts_bench import gen


def generate(seed: int, t: dict, seconds: float) -> gen.Traffic:
    pairs = gen.qos_grid(mixes=t["mixes"], weights=t["weights"],
                         quotas=t["quotas"], rs_caps=t["rs_caps"])
    order = [int(k) for k in
             np.random.default_rng([seed, 0]).permutation(len(pairs))]
    return gen.Traffic([built for built, _ in pairs], [order],
                       policies=[pol for _, pol in pairs])
