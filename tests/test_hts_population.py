"""Population-scale batching: packing/bucketing units (fast tier) and the
one-batch >= 32-scenario differential fuzz (slow tier).

The fast tier pins the shape bookkeeping — padding must be semantics-free,
buckets and chunk plans deterministic, per-scenario slicing identical to
individual runs.  The slow tier drives a whole generated population
through ONE vmapped machine batch and requires bit-identical schedules
against a per-scenario golden loop.
"""
import time

import numpy as np
import pytest

from repro.core import hts
from repro.core.hts import batch, workloads
from repro.core.hts.builder import Program
from repro.core.hts.policy import NO_QUOTA, SchedPolicy

#: one shared shape bucket for every compiled machine in this module —
#: population machines compile per (spec, max_prog, batch width), so the
#: tests reuse a single width/bucket to keep the fast tier fast.
MAX_PROG = 64
N_SMALL = 4


def _tiny(name, n_tasks, kernel="vector_dot", base=0x100):
    p = Program(name, region_base=base)
    frame = p.input(0x10, 4, "frame")
    prev = frame
    for i in range(n_tasks):
        prev = p.task(kernel, in_=prev, out=4, in_size=4, tid=i)
    return p


@pytest.fixture(scope="module")
def small_pop():
    return [_tiny(f"p{i}", 2 + i) for i in range(N_SMALL)]


# ---------------------------------------------------------------------------
# buckets, work estimates and chunk plans (pure shape bookkeeping)
# ---------------------------------------------------------------------------
def test_prog_bucket_ladder():
    assert batch.prog_bucket(0) == batch.MIN_BUCKET
    assert batch.prog_bucket(batch.MIN_BUCKET) == batch.MIN_BUCKET
    assert batch.prog_bucket(batch.MIN_BUCKET + 1) == 2 * batch.MIN_BUCKET
    assert batch.prog_bucket(100) == 128
    assert batch.prog_bucket(5, floor=4) == 8
    with pytest.raises(ValueError):
        batch.prog_bucket(5, floor=0)


def test_work_estimate_tracks_instruction_count(small_pop):
    ests = [batch.work_estimate(p) for p in small_pop]
    assert ests == sorted(ests) and ests[0] < ests[-1]
    # equals the decoded instruction count (the empirically best proxy)
    assert ests[0] == len(small_pop[0].build().instrs)


def test_plan_chunks_partitions_and_sorts(small_pop):
    progs = small_pop * 5                        # 20 scenarios
    plan = batch.plan_chunks(progs, max_chunk=8, min_chunk=2)
    flat = [i for ch in plan for i in ch]
    assert sorted(flat) == list(range(len(progs)))
    # ascending estimated work across the plan
    ests = [batch.work_estimate(progs[i]) for i in flat]
    assert ests == sorted(ests)
    # widths never exceed max_chunk and narrow toward the tail
    widths = [len(ch) for ch in plan]
    assert max(widths) <= 8
    assert widths[0] == 8 and widths == sorted(widths, reverse=True)
    with pytest.raises(ValueError):
        batch.plan_chunks(progs, max_chunk=4, min_chunk=8)


def test_plan_chunks_profile_overrides_static_estimate(small_pop):
    """profile= replaces the instruction-count proxy with measured step
    counts: a profile inverting the static order inverts the plan."""
    n = len(small_pop)
    profile = list(range(n, 0, -1))              # heaviest first by index
    plan = batch.plan_chunks(small_pop, max_chunk=2, min_chunk=1,
                             profile=profile)
    flat = [i for ch in plan for i in ch]
    assert flat == list(reversed(range(n)))      # sorted by profile, not len
    with pytest.raises(ValueError):
        batch.plan_chunks(small_pop, profile=profile[:-1])   # wrong length
    with pytest.raises(ValueError):
        batch.plan_chunks(small_pop, profile=[profile])      # not 1-D


def test_plan_chunks_profile_from_population_result(small_pop):
    """The intended loop: run once, re-chunk on the machine's measured
    per-lane while-loop trip counts (PopulationResult.steps)."""
    first = hts.run_many(small_pop, scheduler="hts_spec")
    steps = first.steps
    assert steps is not None and steps.shape == (len(small_pop),)
    assert (steps >= 1).all()
    # a result object is accepted directly (its .steps is the profile)
    plan = batch.plan_chunks(small_pop, max_chunk=2, min_chunk=1,
                             profile=first)
    flat = [i for ch in plan for i in ch]
    assert sorted(flat) == list(range(len(small_pop)))
    ordered = [int(steps[i]) for i in flat]
    assert ordered == sorted(ordered)            # measured-ascending plan


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------
def test_pack_population_shapes_and_padding(small_pop):
    params = hts.HtsParams()
    pop = batch.pack_population(small_pop, params=params, n_fu=2,
                                max_prog=MAX_PROG)
    n = len(small_pop)
    assert len(pop) == n
    assert pop.ftab.shape[:2] == (n, MAX_PROG)
    assert pop.p_len.tolist() == [len(p.build().instrs) for p in small_pop]
    assert pop.mem.shape == (n, params.total_mem)
    assert pop.n_fu.shape == (n, 10) and (pop.n_fu == 2).all()
    # padding rows are zero (never fetched: pc >= p_len)
    for i in range(n):
        assert (pop.ftab[i, pop.p_len[i]:] == 0).all()
    # auto bucket picks the population's prog_bucket
    auto = batch.pack_population(small_pop)
    assert auto.max_prog == batch.prog_bucket(int(max(auto.p_len)))
    with pytest.raises(ValueError, match="max_prog"):
        batch.pack_population(small_pop, max_prog=4)


def test_pack_population_per_scenario_n_fu_and_policy(small_pop):
    fus = [1, 2, (1,) * 10, 4]
    pols = [None, SchedPolicy.of(weights={1: 3}), None,
            SchedPolicy.of(quotas={2: 1}, rs_caps={3: 2})]
    pop = batch.pack_population(small_pop, n_fu=fus, policy=pols,
                                max_prog=MAX_PROG)
    assert pop.n_fu[0].tolist() == [1] * 10
    assert pop.n_fu[3].tolist() == [4] * 10
    assert pop.prio[1][1] == 3 and pop.prio[0][1] == 0
    assert pop.quota[3][2] == 1 and pop.rs_cap[3][3] == 2
    assert pop.rs_cap[0][3] == NO_QUOTA
    assert pop.widest_fu == 4
    with pytest.raises(ValueError, match="n_fu"):
        batch.pack_population(small_pop, n_fu=[1, 2])
    with pytest.raises(ValueError, match="policies"):
        batch.pack_population(small_pop, policy=[None])


def test_padding_is_semantics_free(small_pop):
    """The same program, padded to two different buckets, schedules
    identically (padding rows are never fetched)."""
    a = hts.run(small_pop[0], n_fu=2, max_prog=32, max_fu_per_class=2)
    b = hts.run(small_pop[0], n_fu=2, max_prog=MAX_PROG, max_fu_per_class=2)
    assert a.cycles == b.cycles and a.schedule == b.schedule


# ---------------------------------------------------------------------------
# run_many and PopulationResult
# ---------------------------------------------------------------------------
def test_run_many_matches_individual_runs(small_pop):
    pr = hts.run_many(small_pop, n_fu=2, max_prog=MAX_PROG)
    assert len(pr) == N_SMALL and pr.all_halted
    for i, prog in enumerate(small_pop):
        solo = hts.run(prog, n_fu=2, max_prog=MAX_PROG,
                       max_fu_per_class=pr.max_fu_per_class)
        assert solo.cycles == int(pr.cycles[i])
        assert solo.schedule == pr[i].schedule       # per-scenario slicing
        assert pr[i].program == f"p{i}"
    # iteration yields the same Results; table renders
    assert [r.cycles for r in pr] == [int(c) for c in pr.cycles]
    assert "scenario" in pr.table()
    assert pr.scenarios_per_sec() > 0


def test_run_many_golden_backend_parity(small_pop):
    gr = hts.run_many(small_pop, n_fu=2, backend="golden")
    jr = hts.run_many(small_pop, n_fu=2, max_prog=MAX_PROG)
    assert gr.backend == "golden" and len(gr) == len(jr)
    assert [int(c) for c in gr.cycles] == [int(c) for c in jr.cycles]
    assert gr[0].schedule == jr[0].schedule
    with pytest.raises(ValueError, match="backend"):
        hts.run_many(small_pop, backend="nope")


def test_run_many_per_scenario_policies(small_pop):
    """One batched call, a different policy per lane — same results as
    per-scenario runs with those policies."""
    pols = [SchedPolicy(), SchedPolicy.of(weights={1: 8}),
            SchedPolicy.of(rs_caps={1: 1}), SchedPolicy.of(quotas={1: 1})]
    pr = hts.run_many(small_pop, n_fu=2, policy=pols, max_prog=MAX_PROG)
    for i, prog in enumerate(small_pop):
        solo = hts.run(prog, n_fu=2, policy=pols[i], max_prog=MAX_PROG,
                       max_fu_per_class=pr.max_fu_per_class)
        assert solo.schedule == pr[i].schedule, i


@pytest.mark.parametrize("backend", ["jax", "golden"])
def test_run_many_pack_us_times_the_pack(small_pop, backend):
    """``pack_us`` is the ``hts.pack`` span: positive when ``run_many``
    packs, inside the call's own wall, and apart from the launch's
    ``wall_us``."""
    t0 = time.perf_counter()
    pr = hts.run_many(small_pop, n_fu=2, max_prog=MAX_PROG, backend=backend)
    call_us = (time.perf_counter() - t0) * 1e6
    assert 0.0 < pr.pack_us <= call_us
    assert pr.pack_us + pr.wall_us <= call_us


@pytest.mark.parametrize("backend", ["jax", "golden"])
def test_run_many_prepacked_has_no_pack_time(small_pop, backend):
    pop = batch.pack_population(small_pop, n_fu=2, max_prog=MAX_PROG)
    pr = hts.run_many(pop, backend=backend)
    assert pr.pack_us == 0.0 and pr.wall_us > 0.0
    assert [int(c) for c in pr.cycles] == [
        int(c) for c in hts.run_many(small_pop, n_fu=2, max_prog=MAX_PROG,
                                     backend=backend).cycles]


def test_sweep_population_mode(small_pop):
    sw = hts.sweep(small_pop[:2], n_fu=(1, 2), schedulers=("hts_spec",),
                   max_prog=MAX_PROG)
    assert sw.is_population and sw.programs == ("p0", "p1")
    assert sw.cycles["hts_spec"].shape == (2, 2)
    # more units never slows a scenario down
    assert (sw.cycles["hts_spec"][:, 0] >= sw.cycles["hts_spec"][:, 1]).all()
    assert "scenarios" in sw.table()


def test_compare_population_mode(small_pop):
    report = hts.compare(small_pop, schedulers=("hts_spec",),
                         max_prog=MAX_PROG)
    assert isinstance(report, hts.PopulationCompareReport)
    assert len(report) == N_SMALL and report.n_modes == 3
    assert report.cycles["hts_spec"].shape == (N_SMALL,)


def test_compare_population_raises_on_injected_divergence(small_pop):
    """A wrong golden row must surface as a MismatchError naming the
    scenario (guards the comparison itself, not just happy paths)."""
    import repro.core.hts.api as api
    real = api.run_many

    def crooked(programs, **kw):
        res = real(programs, **kw)
        if kw.get("backend") == "golden":
            object.__setattr__(res, "cycles", res.cycles + 1)
        return res

    api.run_many, saved = crooked, api.run_many
    try:
        with pytest.raises(hts.MismatchError, match="scenario 0"):
            api.compare_population(small_pop, schedulers=("hts_spec",),
                                   max_prog=MAX_PROG)
    finally:
        api.run_many = saved


# ---------------------------------------------------------------------------
# slow tier: one >= 32-scenario vmap batch, bit-identical to golden
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_population_differential_fuzz_one_batch():
    """>= 32 generated scenarios simulated in ONE vmap batch; schedules
    must be bit-identical to per-scenario golden runs (and to the
    no-event-skip machine), per scheduler."""
    (pop,) = workloads.generate_population(32, bucket=False,
                                           kernels=workloads.CHEAP_MIX,
                                           max_tasks=4)
    report = hts.compare(list(pop.programs), n_fu=2,
                         schedulers=("naive", "hts_spec"),
                         max_prog=pop.max_prog)
    assert len(report) == 32 and report.n_modes == 3


@pytest.mark.slow
def test_population_mixed_priority_differential_fuzz():
    """Mixed-priority population (weights/quotas/RS caps drawn per
    scenario) through the batched machine vs golden."""
    (pop,) = workloads.generate_population(16, bucket=False,
                                           kernels=workloads.CHEAP_MIX,
                                           max_tasks=4, mixed_priority=True)
    assert any(sc.policy is not None and sc.policy.rs_caps
               for sc in pop.scenarios), "no RS cap drawn in 16 scenarios"
    report = hts.compare(list(pop.programs), n_fu=2,
                         schedulers=("hts_spec",), max_prog=pop.max_prog)
    assert len(report) == 16


@pytest.mark.slow
def test_population_heterogeneous_differential_fuzz():
    """Heterogeneous population: per-scenario cost tables (mixed with
    uniform lanes and eft policies) ride the same vmap batch as the
    mixed-priority tables, golden = machine in every event-skip mode."""
    (pop,) = workloads.generate_population(16, bucket=False,
                                           kernels=workloads.CHEAP_MIX,
                                           max_tasks=4, mixed_priority=True,
                                           heterogeneous_fus=True)
    scs = pop.scenarios
    assert any(sc.fu_cost is not None for sc in scs)
    assert any(sc.policy and sc.policy.issue_mode == "eft" for sc in scs)
    report = hts.compare(list(pop.programs), n_fu=2,
                         fu_cost=[sc.fu_cost for sc in scs],
                         schedulers=("hts_spec",), max_prog=pop.max_prog)
    assert len(report) == 16
