"""Priority/quota-aware scheduling: SchedPolicy semantics, starvation
recovery (weighted arbiter provably reorders issue), quota-mask invariants
(per-pid per-class in-flight cap never exceeded), policy threading through
builder/api, and the mixed-priority differential fuzzer (golden ≡ JAX
machine, event-skip on and off)."""
import numpy as np
import pytest

from repro.core import hts
from repro.core.hts import workloads
from repro.core.hts.builder import BuilderError, Program
from repro.core.hts.policy import NO_QUOTA, NUM_PIDS, PRIO_CAP, SchedPolicy

#: acceptance floor for the mixed-priority differential fuzz (fast tier).
PRIORITY_FUZZ_SEEDS = 25
FUZZ_SCHEDULERS = ("naive", "hts_nospec", "hts_spec")


# ---------------------------------------------------------------------------
# scenario builders (the benchmark's starvation shape, sized for tests)
# ---------------------------------------------------------------------------
def _hi_chain(chain=8, delay=8, func="dct"):
    """Latency-sensitive tenant: RAW chain, arriving after `delay` nops."""
    p = Program("hi", region_base=0x100)
    frame = p.input(0x10, 4, "frame")
    for _ in range(delay):
        p.nop()
    with p.process(1):
        prev = frame
        for i in range(chain):
            prev = p.task(func, in_=prev, out=4, in_size=4, tid=i)
    return p


def _greedy(pid, tasks=8, func="dct"):
    """Best-effort flood: independent same-class tasks."""
    p = Program(f"greedy{pid}", region_base=0x200 + 0x100 * (pid - 2))
    frame = p.input(0x10, 4, "frame")
    with p.process(pid):
        for i in range(tasks):
            p.task(func, in_=frame, out=4, tid=i & 0xF)
    return p


def _contended(n_greedy=2, *, priorities=None, quotas=None, **hi_kw):
    return Program.merge(
        [_hi_chain(**hi_kw)] + [_greedy(2 + k) for k in range(n_greedy)],
        "contended", require_distinct_pids=True,
        priorities=priorities, quotas=quotas)


def _max_inflight(result, pid, func):
    """Peak concurrently-executing tasks of (pid, func) in a schedule."""
    iv = [(r.issue, r.complete) for r in result.schedule
          if r.pid == pid and r.func == func
          and not r.aborted and r.issue >= 0 and r.complete >= 0]
    points = sorted({t for s, e in iv for t in (s, e)})
    return max((sum(1 for s, e in iv if s <= t < e) for t in points),
               default=0)


# ---------------------------------------------------------------------------
# SchedPolicy semantics
# ---------------------------------------------------------------------------
def test_policy_tables_and_defaults():
    pol = SchedPolicy.of(weights={1: 8, 3: 2}, quotas={2: 1})
    assert pol.weight_of(1) == 8 and pol.weight_of(2) == 0
    assert pol.quota_of(2) == 1 and pol.quota_of(1) == NO_QUOTA
    w = pol.weight_array()
    q = pol.quota_array()
    assert w.shape == (NUM_PIDS,) and q.shape == (NUM_PIDS,)
    assert w[1] == 8 and w[0] == 0 and q[2] == 1 and q[0] == NO_QUOTA
    assert not pol.is_default and SchedPolicy().is_default
    # hashable + content-equal (usable inside frozen HtsParams)
    assert pol == SchedPolicy.of(weights={3: 2, 1: 8}, quotas={2: 1})
    assert hash(pol) == hash(SchedPolicy.of(weights={3: 2, 1: 8},
                                            quotas={2: 1}))
    with pytest.raises(ValueError):
        SchedPolicy.of(weights={16: 1})          # pid outside 4-bit field
    with pytest.raises(ValueError):
        SchedPolicy.of(quotas={1: 0})            # quota must be >= 1
    with pytest.raises(ValueError):
        SchedPolicy.of(weights={1: PRIO_CAP + 1})  # beyond arbiter precision


def test_policy_issue_key_orders_priority_then_age():
    pol = SchedPolicy.of(weights={1: 4, 2: 1})
    # higher weight beats lower weight regardless of age
    assert pol.issue_key(1, age=100) < pol.issue_key(2, age=0)
    # age breaks ties within a priority class
    assert pol.issue_key(2, age=3) < pol.issue_key(2, age=4)
    assert pol.issue_key(2, age=3) < pol.issue_key(0, age=0)  # w=1 > w=0


def test_policy_merge_with_unions_and_rejects_conflicts():
    a = SchedPolicy.of(weights={1: 8})
    b = SchedPolicy.of(weights={2: 2}, quotas={3: 1})
    u = a.merge_with(b)
    assert u.weight_of(1) == 8 and u.weight_of(2) == 2 and u.quota_of(3) == 1
    with pytest.raises(ValueError, match="conflicting weight"):
        a.merge_with(SchedPolicy.of(weights={1: 2}))


# ---------------------------------------------------------------------------
# starvation recovery: weighted arbiter provably reorders issue
# ---------------------------------------------------------------------------
def test_priority_weighting_recovers_starved_tenant():
    """The late-arriving chain is starved by age order; a priority weight
    strictly drops its makespan to within 15% of its solo run, while the
    shared run's total cycles regress < 5% (here: don't regress at all)."""
    solo = hts.run(_hi_chain(), n_fu=1)
    base = hts.run(_contended(2), n_fu=1)
    prio = hts.run(_contended(2, priorities={1: 8}), n_fu=1)
    solo_mk = solo.app_makespan(1)
    assert base.app_makespan(1) > 2 * solo_mk          # provably starved
    assert prio.app_makespan(1) < base.app_makespan(1)  # strictly reordered
    assert prio.app_makespan(1) <= 1.15 * solo_mk       # QoS recovered
    assert prio.cycles <= 1.05 * base.cycles            # work-conserving

    # the high-priority pid's tasks overtake older greedy tasks in issue
    # order — impossible under pure age arbitration
    hi_rows = prio.schedule_for(1)
    greedy_uid_after = [r for r in prio.schedule
                        if r.pid != 1 and r.uid < hi_rows[-1].uid
                        and r.issue > hi_rows[-1].issue]
    assert greedy_uid_after, "no older greedy task issued after the chain"


def test_priority_is_runtime_data_same_compiled_machine():
    """Distinct policies reuse one compiled machine (weights are traced)."""
    from repro.core.hts import machine
    machine._compiled.cache_clear()
    prog = _contended(2)
    hts.run(prog, n_fu=1, policy=SchedPolicy.of(weights={1: 1}))
    misses_after_first = machine._compiled.cache_info().misses
    hts.run(prog, n_fu=1, policy=SchedPolicy.of(weights={1: 7}, quotas={2: 1}))
    assert machine._compiled.cache_info().misses == misses_after_first


# ---------------------------------------------------------------------------
# quota-mask invariants
# ---------------------------------------------------------------------------
DCT = 8     # costs.FUNC_IDS["dct"]


@pytest.mark.parametrize("cap", [1, 2])
def test_quota_never_exceeded(cap):
    """Per-pid per-class in-flight units never exceed the cap, on both
    backends, while uncapped pids are free to exceed it."""
    prog = _contended(2, quotas={2: cap, 3: cap})
    for backend in ("jax", "golden"):
        r = hts.run(prog, n_fu=4, backend=backend)
        for pid in (2, 3):
            assert _max_inflight(r, pid, DCT) <= cap, (backend, pid)
    # sanity: the cap binds — without it the flood takes > cap units
    r0 = hts.run(_contended(2), n_fu=4)
    assert max(_max_inflight(r0, pid, DCT) for pid in (2, 3)) > 1


def test_quota_reserves_capacity_when_caps_below_pool():
    """Sum of greedy caps < n_fu leaves a unit for the uncapped tenant:
    its chain runs at (near-)solo speed with no priority weight at all."""
    solo = hts.run(_hi_chain(), n_fu=3)
    base = hts.run(_contended(2), n_fu=3)
    quot = hts.run(_contended(2, quotas={2: 1, 3: 1}), n_fu=3)
    assert quot.app_makespan(1) < base.app_makespan(1)
    assert quot.app_makespan(1) <= 1.15 * solo.app_makespan(1)


# ---------------------------------------------------------------------------
# RS admission control (per-pid reservation-station entry caps)
# ---------------------------------------------------------------------------
# the RS-residency metric is shared with the benchmark that commits the
# rs_admission numbers — one definition of "the cap binds" for both
from benchmarks.priority import _max_rs_occupancy  # noqa: E402


def test_rs_cap_policy_semantics():
    pol = SchedPolicy.of(rs_caps={2: 3})
    assert pol.rs_cap_of(2) == 3 and pol.rs_cap_of(1) == NO_QUOTA
    arr = pol.rs_cap_array()
    assert arr.shape == (NUM_PIDS,) and arr[2] == 3 and arr[0] == NO_QUOTA
    assert not pol.is_default and "rs_caps" in pol.describe()
    with pytest.raises(ValueError):
        SchedPolicy.of(rs_caps={1: 0})           # cap must be >= 1
    u = SchedPolicy.of(weights={1: 8}).merge_with(pol)
    assert u.rs_cap_of(2) == 3 and u.weight_of(1) == 8
    with pytest.raises(ValueError, match="conflicting rs_cap"):
        pol.merge_with(SchedPolicy.of(rs_caps={2: 1}))


@pytest.mark.parametrize("cap", [1, 3])
def test_rs_cap_never_exceeded(cap):
    """Per-pid RS residency never exceeds the admission cap, on both
    backends; uncapped pids are free to exceed it."""
    prog = _contended(2)
    pol = SchedPolicy.of(rs_caps={2: cap, 3: cap})
    for backend in ("jax", "golden"):
        r = hts.run(prog, n_fu=1, backend=backend, policy=pol)
        for pid in (2, 3):
            assert _max_rs_occupancy(r, pid) <= cap, (backend, pid)
    # sanity: the cap binds — without it the flood holds > cap entries
    r0 = hts.run(_contended(2), n_fu=1)
    assert max(_max_rs_occupancy(r0, pid) for pid in (2, 3)) > 3


def test_rs_cap_differential_and_merge_attach():
    """RS-capped arbitration is verified by the same golden ≡ machine
    machinery (event-skip on and off), and ``merge(rs_caps=...)``
    attaches the policy to the program."""
    prog = Program.merge(
        [_hi_chain(chain=4, delay=4)] + [_greedy(2 + k, 6) for k in range(2)],
        "capped", require_distinct_pids=True,
        priorities={1: 8}, rs_caps={2: 2, 3: 2})
    assert prog.policy == SchedPolicy.of(weights={1: 8},
                                         rs_caps={2: 2, 3: 2})
    report = hts.compare(prog, schedulers=("naive", "hts_spec"), n_fu=1)
    assert report.schedulers == ("naive", "hts_spec")


def test_rs_cap_bounds_flood_occupancy_but_not_stream_position():
    """The measured finding behind BENCH_priority.json's rs_admission
    section: caps bound the flood's window residency (the admission
    mechanism works) but cannot improve the late tenant's makespan in the
    merged-stream model — dispatch order IS stream order, so a blocking
    cap can only delay instructions, never reorder them.  The honest
    comparison: occupancy drops, hi makespan does not improve."""
    base = hts.run(_contended(2), n_fu=1, policy=SchedPolicy.of(
        weights={1: 8}))
    capped = hts.run(_contended(2), n_fu=1, policy=SchedPolicy.of(
        weights={1: 8}, rs_caps={2: 2, 3: 2}))
    assert max(_max_rs_occupancy(capped, pid) for pid in (2, 3)) <= 2
    assert max(_max_rs_occupancy(base, pid) for pid in (2, 3)) > 2
    assert capped.app_makespan(1) >= base.app_makespan(1)


# ---------------------------------------------------------------------------
# policy threading: builder → api → Result/FairnessReport
# ---------------------------------------------------------------------------
def test_merge_attaches_policy_and_run_applies_it():
    prog = _contended(2, priorities={1: 8}, quotas={2: 1})
    assert prog.policy == SchedPolicy.of(weights={1: 8}, quotas={2: 1})
    r = hts.run(prog, n_fu=1)                    # picked up automatically
    assert r.policy is prog.policy
    # explicit policy= argument overrides the attached one
    r2 = hts.run(prog, n_fu=1, policy=SchedPolicy())
    assert r2.policy.is_default
    assert r2.schedule == hts.run(_contended(2), n_fu=1).schedule


def test_merge_unions_tenant_policies_and_rejects_conflicts():
    a = _hi_chain()
    a.policy = SchedPolicy.of(weights={1: 8})
    b = _greedy(2)
    b.policy = SchedPolicy.of(quotas={2: 1})
    merged = Program.merge([a, b], require_distinct_pids=True)
    assert merged.policy == SchedPolicy.of(weights={1: 8}, quotas={2: 1})
    b.policy = SchedPolicy.of(weights={1: 2})    # conflicts with a
    with pytest.raises(BuilderError, match="conflicting weight"):
        Program.merge([a, b], require_distinct_pids=True)


def test_fairness_report_carries_weights():
    sc = workloads.generate_scenario(17, n_tenants=3,
                                     kernels=workloads.CHEAP_MIX,
                                     mixed_priority=True)
    assert sc.policy is not None and not sc.policy.is_default
    shared = hts.run(sc.merged, n_fu=1)
    fair = shared.fairness(workloads.solo_results(sc, n_fu=1))
    assert fair.weights == {pid: sc.policy.weight_of(pid) for pid in sc.pids}
    by_w = fair.by_weight()
    assert list(by_w) == sorted(by_w, reverse=True)
    assert "weight" in fair.table()
    # same seed without mixed_priority generates identical tenant programs
    plain = workloads.generate_scenario(17, n_tenants=3,
                                        kernels=workloads.CHEAP_MIX)
    assert plain.merged.asm == sc.merged.asm and plain.policy is None


def test_qos_grid_slice_run_many_matches_golden():
    """The QoS grid's 5-flood mix under all 16 of its policies (pid 1's
    weight x the floods' FU quota x their RS cap), one lane each in one
    ``run_many`` call, agrees row for row with the golden loop."""
    from benchmarks import population
    grid = population.build_grid(mixes=(5,))
    assert len(grid) == 16
    progs, pols = zip(*grid)
    params = hts.HtsParams(max_tasks=192, cdb_entries=64)
    kw = dict(n_fu=2, params=params, policy=list(pols))
    got = hts.run_many(list(progs), max_prog=128, **kw)
    want = hts.run_many(list(progs), backend="golden", **kw)
    assert got.all_halted and want.all_halted
    for i in range(len(grid)):
        assert got[i].cycles == want[i].cycles, i
        assert got[i].schedule == want[i].schedule, i
    # the policies bind: the lanes do not all schedule alike
    assert len({r.schedule for r in want}) > 1


# ---------------------------------------------------------------------------
# the mixed-priority differential fuzzer (fast tier: >= 25 seeds)
# ---------------------------------------------------------------------------
def test_fuzz_differential_mixed_priority():
    passed = 0
    for seed in range(PRIORITY_FUZZ_SEEDS):
        sc = workloads.generate_scenario(seed, n_tenants=2 + seed % 3,
                                         kernels=workloads.CHEAP_MIX,
                                         max_tasks=4, mixed_priority=True)
        assert sc.policy is not None
        report = hts.compare(sc.merged, schedulers=FUZZ_SCHEDULERS)
        assert report.schedulers == FUZZ_SCHEDULERS
        passed += 1
    assert passed >= 25


@pytest.mark.slow
def test_fuzz_differential_mixed_priority_heavy():
    """Slow tier: full Table-II kernel mix, up to 8 tenants, software
    scheduler included, wider FU pools."""
    for seed in range(10):
        sc = workloads.generate_scenario(2000 + seed,
                                         kernels=workloads.FULL_MIX,
                                         mixed_priority=True)
        hts.compare(sc.merged, n_fu=3,
                    schedulers=("naive", "software", "hts_nospec",
                                "hts_spec"))
