"""The benchmark's plain reference agrees with the repository's golden
simulator on the deployments it covers (a test of the reference, which
itself imports nothing of the system under test)."""
import json
import pathlib

import numpy as np
import pytest

from hts_bench import common, gen

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _golden(built, scheduler, n_fu, params, policy=None):
    from repro.core import hts
    r = hts.run(built, scheduler=scheduler, n_fu=n_fu, backend="golden",
                params=params, policy=policy)
    return r.cycles, r.halted, r.schedule_tuple()


def _random_policy(rng, pids):
    """Weights, FU quotas and RS caps on a random subset of ``pids`` each,
    as plain data with JSON's string keys."""
    def some(values):
        return {str(p): int(rng.choice(values)) for p in pids
                if rng.random() < 0.5}
    return {"weights": some([0, 1, 3, 8, 4096]), "quotas": some([1, 2]),
            "rs_caps": some([1, 2, 4])}


@pytest.mark.parametrize("seed", range(6))
def test_generated_scenarios(seed):
    from repro.core import hts
    cfg = _cfg("generated_mix")
    built = gen.scenario([seed, 1], kernels=list(gen.MIX), max_tasks=4)\
        .build()
    want = common.expect(built, cfg, "hts_spec", 2)
    got = _golden(built, "hts_spec", 2, hts.HtsParams(**cfg["hts"]))
    assert not common.differs(*got, want)


@pytest.mark.parametrize("name", sorted(gen.PAPER_PROGRAMS))
@pytest.mark.parametrize("scheduler,n_fu", [("naive", 1), ("hts_spec", 2)])
def test_paper_programs(name, scheduler, n_fu):
    from repro.core import hts
    cfg = _cfg("paper_eval")
    built = gen.PAPER_PROGRAMS[name]().build()
    want = common.expect(built, cfg, scheduler, n_fu)
    got = _golden(built, scheduler, n_fu, hts.HtsParams(**cfg["hts"]))
    assert not common.differs(*got, want)


#: the grid's 5-flood mix: its 16 policies (``gen.qos_grid``'s last 16)
GRID_5 = list(range(48, 64))


@pytest.mark.parametrize("index", GRID_5)
def test_qos_grid_policies(index):
    from repro.core import hts
    cfg = _cfg("generated_mix")
    built, policy = gen.qos_grid()[index]
    want = common.expect(built, cfg, "hts_spec", 2, policy=policy)
    got = _golden(built, "hts_spec", 2,
                  hts.HtsParams(**cfg["hts"], max_tasks=192, cdb_entries=64),
                  hts.SchedPolicy.of(**policy))
    assert want.halted
    assert not common.differs(*got, want)


@pytest.mark.parametrize("seed", range(6))
def test_random_policies(seed):
    from repro.core import hts
    cfg = _cfg("generated_mix")
    built = gen.scenario([seed, 1], kernels=list(gen.MIX), max_tasks=4)\
        .build()
    policy = _random_policy(np.random.default_rng([seed, 5]), range(1, 9))
    want = common.expect(built, cfg, "hts_spec", 2, policy=policy)
    got = _golden(built, "hts_spec", 2, hts.HtsParams(**cfg["hts"]),
                  hts.SchedPolicy.of(**policy))
    assert want.halted
    assert not common.differs(*got, want)


@pytest.mark.parametrize("policy", [{}, {"quotas": {2: 1}},
                                    {"quotas": {2: 1}, "weights": {2: 8}}])
def test_quota_counts_units_issued_in_the_same_cycle(policy):
    """Three dct tasks of one pid wait on one producer, so they become
    ready in one cycle with both dct units free: a quota of 1 lets one
    issue."""
    from repro.core import hts
    cfg = _cfg("generated_mix")
    p = hts.Program("fanout_after_one")
    frame = p.input(gen.INPUT, gen.INPUT_WORDS, "frame")
    with p.process(2):
        h = p.task("fft_256", in_=frame, out=4, in_size=4, tid=0)
        for i in range(3):
            p.task("dct", in_=h, out=4, in_size=4, tid=1 + i)
    built = p.build()
    want = common.expect(built, cfg, "hts_spec", 2, policy=policy)
    got = _golden(built, "hts_spec", 2, hts.HtsParams(**cfg["hts"]),
                  hts.SchedPolicy.of(**policy))
    assert not common.differs(*got, want)
    issues = sorted(r[3] for r in want.rows if r[1] == 8)
    assert (issues[0] == issues[1]) == ("quotas" not in policy), issues


@pytest.mark.parametrize("seed", range(6))
def test_default_policy_is_age_order(seed):
    cfg = _cfg("generated_mix")
    built = gen.scenario([seed, 1], kernels=list(gen.MIX), max_tasks=4)\
        .build()
    default = {"weights": {1: 0, "2": 0}, "quotas": {}, "rs_caps": {},
               "fe_mode": "rr", "issue_mode": "greedy"}
    assert common.expect(built, cfg, "hts_spec", 2, policy=default) == \
        common.expect(built, cfg, "hts_spec", 2)


@pytest.mark.parametrize("policy,why", [
    ({"issue_mode": "eft"}, "identical"),
    ({"fe_mode": "weighted"}, "merged dispatch stream"),
    ({"default_weight": 2}, "not modelled"),
    ({"quotas": {1: 0}}, "under 1"),
])
def test_refuses_what_it_does_not_model(policy, why):
    cfg = _cfg("generated_mix")
    built = gen.no_dependency(2).build()
    with pytest.raises(ValueError, match=why):
        common.expect(built, cfg, "hts_spec", 2, policy=policy)
