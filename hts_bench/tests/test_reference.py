"""The benchmark's plain reference agrees with the repository's golden
simulator on the deployments it covers (a test of the reference, which
itself imports nothing of the system under test)."""
import json
import pathlib

import pytest

from hts_bench import common, gen

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _golden(built, scheduler, n_fu, params):
    from repro.core import hts
    r = hts.run(built, scheduler=scheduler, n_fu=n_fu, backend="golden",
                params=params)
    return r.cycles, r.halted, r.schedule_tuple()


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
