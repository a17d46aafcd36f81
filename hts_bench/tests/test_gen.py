"""The copied generators build the same programs as the repository's."""
import numpy as np
import pytest

from hts_bench import gen


def _same(a, b):
    assert np.array_equal(a.code, b.code)
    assert a.mem_init == b.mem_init
    assert a.effects == b.effects


@pytest.mark.parametrize("seed", [0, 1, 7, 123, 2024])
@pytest.mark.parametrize("kw", [
    dict(kernels=("vector_dot", "vector_add", "vector_max", "dct",
                  "correlation"), max_tasks=4),
    dict(kernels=("vector_dot", "vector_add", "vector_max", "dct",
                  "correlation", "real_fir", "iir"), max_tasks=5,
         n_tenants=2),
])
def test_scenario_matches_workloads(seed, kw):
    from repro.core.hts import workloads
    want = workloads.generate_scenario(seed, **kw).merged.program.build()
    _same(gen.scenario(seed, **kw).build(), want)


def test_paper_programs_match_all_benches():
    from repro.core.hts import programs
    want = {b.name: b.program.build() for b in programs.all_benches()}
    assert set(want) == set(gen.PAPER_PROGRAMS)
    for name, build in gen.PAPER_PROGRAMS.items():
        _same(build().build(), want[name])


@pytest.mark.parametrize("seed", [3, 99])
def test_random_dependency_matches(seed):
    from repro.core.hts import programs
    _same(gen.random_dependency(seed=seed).build(),
          programs.random_dependency(seed=seed).program.build())


def test_qos_grid_matches_build_grid():
    from benchmarks import population
    from repro.core import hts
    want = population.build_grid()
    got = gen.qos_grid()
    assert len(got) == len(want) == 64
    for (built, policy), (built_0, policy_0) in zip(got, want):
        _same(built, built_0)
        assert hts.SchedPolicy.of(**policy) == policy_0


def test_traffic_depends_on_the_seed_alone():
    t = {"generator": "pool", "pool": 4, "n_tenants": 2,
         "kernels": ["dct", "vector_dot"], "max_tasks": 3,
         "arrivals": {"kind": "poisson", "rate_per_s": 50},
         "library_seed": 0}
    a, b = gen.generate(2**31 + 5, t, 2.0), gen.generate(2**31 + 5, t, 2.0)
    assert np.array_equal(a.arrivals, b.arrivals)
    assert np.array_equal(a.requests, b.requests)
    for x, y in zip(a.programs, b.programs):
        _same(x, y)
    assert a.arrivals.max() < 2.0


def test_every_seed_offers_the_same_work_in_another_order():
    t = {"generator": "pool", "pool": 4, "n_tenants": 2,
         "kernels": ["dct", "vector_dot"], "max_tasks": 3,
         "arrivals": {"kind": "poisson", "rate_per_s": 50},
         "library_seed": 0}
    a, b = gen.generate(1, t, 2.0), gen.generate(2, t, 2.0)
    assert np.array_equal(a.arrivals, b.arrivals)
    assert sorted(a.requests) == sorted(b.requests)
    assert not np.array_equal(a.requests, b.requests)


def test_a_new_kind_is_a_file_of_its_own(tmp_path, monkeypatch):
    """A generator kind and an arrival kind are found by name, as a file
    beside the others, with no edit to ``gen.py``."""
    import hts_bench.arrivals
    import hts_bench.generators
    (tmp_path / "gen_kind").mkdir()
    (tmp_path / "gen_kind" / "ticks.py").write_text(
        "import numpy as np\n"
        "def due_times(rng, a, seconds):\n"
        "    return np.arange(0.0, 2 * seconds, a['every_s'])\n")
    (tmp_path / "gen_kind" / "one_each.py").write_text(
        "import numpy as np\n"
        "from hts_bench import gen\n"
        "def generate(seed, t, seconds):\n"
        "    due = gen.due_times(np.random.default_rng(seed), t['arrivals'],"
        " seconds)\n"
        "    return gen.Traffic([], arrivals=due,"
        " requests=np.zeros(len(due), int))\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setattr(hts_bench.arrivals, "__path__",
                        [*hts_bench.arrivals.__path__,
                         str(tmp_path / "gen_kind")])
    monkeypatch.setattr(hts_bench.generators, "__path__",
                        [*hts_bench.generators.__path__,
                         str(tmp_path / "gen_kind")])
    t = {"generator": "one_each",
         "arrivals": {"kind": "ticks", "every_s": 0.5}}
    got = gen.generate(1, t, 2.0)
    assert np.array_equal(got.arrivals, [0.0, 0.5, 1.0, 1.5])
