"""step_impl backends: xla (restructured), xla_base, pallas — bit-identity.

The PR that introduced ``step_impl`` restructured the XLA step body for
the population width-cost curve and added a fused-pallas-kernel step
(interpreted on CPU).  Every implementation must produce bit-identical
schedules: these tests pin xla ≡ xla_base ≡ pallas on generated
scenarios (cycles, full schedule tuples, fe_stall; both event-skip
modes; single-lane and population paths), pallas ≡ golden through the
standard differential machinery (slow tier — interpret mode pays per
step), and the compile-bucket invariant that the default path did not
move.  The completion write-back and the TM drain copy one memory window
per completing unit or drain (``xla_base`` copies one per unit every
trip): programs where several units of a lane complete on one trip, and
one that drains the TM, pin that loop against ``xla_base`` and golden.
"""
import numpy as np
import pytest

import collections

import repro.core.hts as hts
from repro.core.hts import api, batch, costs, machine, programs, workloads
from repro.core.hts.builder import Program

FAST_SEEDS = (0, 3, 11)


def _prep(seed, **kw):
    sc = workloads.generate_scenario(seed, n_tenants=2 + seed % 3,
                                     kernels=workloads.CHEAP_MIX,
                                     max_tasks=4, **kw)
    return sc, api._prepare(sc.merged)


# ---------------------------------------------------------------------------
# cross-implementation bit-identity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("event_skip", [True, False])
def test_step_impls_bit_identical_single_lane(event_skip):
    """All three implementations agree on every output array of the
    single-lane machine — trace tables and counters included, so
    fe_stall/stall_cycles/fu_busy_cycles are pinned, not just cycles."""
    cost = costs.costs_by_name("hts_spec")
    for seed in FAST_SEEDS:
        sc, prep = _prep(seed, heterogeneous_fus=seed % 2 == 1)
        outs = {impl: machine.simulate(prep.code, cost,
                                       mem_init=prep.mem_init,
                                       effects=prep.effects,
                                       event_skip=event_skip,
                                       fu_cost=sc.fu_cost, step_impl=impl)
                for impl in machine.STEP_IMPLS}
        ref = outs["xla"]
        for impl in ("xla_base", "pallas"):
            for k in ref:
                assert np.array_equal(ref[k], outs[impl][k]), \
                    f"seed {seed}: xla vs {impl} differ on {k!r}"


def test_step_impls_bit_identical_population():
    """One packed population through run_many under each implementation:
    cycles, schedule tuples and fe_stall agree lane for lane."""
    progs = [_prep(s)[0].merged for s in range(4)]
    runs = {impl: hts.run_many(progs, scheduler="hts_spec", step_impl=impl)
            for impl in machine.STEP_IMPLS}
    ref = runs["xla"]
    for impl in ("xla_base", "pallas"):
        r = runs[impl]
        assert np.array_equal(ref.cycles, r.cycles), impl
        for i in range(len(progs)):
            assert ref[i].schedule_tuple() == r[i].schedule_tuple(), \
                (impl, i)
            assert ref[i].fe_stall == r[i].fe_stall, (impl, i)


def test_single_lane_pallas_via_api():
    """hts.run(step_impl="pallas") — the population-of-one lift — matches
    the default path on the full Result surface."""
    sc, _ = _prep(7)
    a = hts.run(sc.merged, scheduler="hts_spec")
    b = hts.run(sc.merged, scheduler="hts_spec", step_impl="pallas")
    assert a.cycles == b.cycles
    assert a.schedule_tuple() == b.schedule_tuple()
    assert a.fe_stall == b.fe_stall


def test_pallas_resumable_slices_compose():
    """The pallas step is a fixed point for paused lanes too: slicing a
    pallas population in small step budgets collects the same outcome as
    the unsliced pallas (and default xla) run."""
    import jax
    import jax.numpy as jnp
    progs = [_prep(s)[0].merged for s in range(3)]
    ref = hts.run_many(progs, scheduler="hts_spec")
    pal = hts.run_many(progs, scheduler="hts_spec", step_impl="pallas")
    rm = api._population_slicer(pal._spec, pal._max_prog)
    args = [jnp.asarray(a) for a in pal._margs]
    carry = rm.init(*args)
    for _ in range(200):
        carry = rm.run_slice(carry, *args, jnp.asarray(37, jnp.int32))
        if not np.asarray(jax.device_get(carry["halted"]) == False).any():
            break
    out = rm.collect(carry)
    assert np.array_equal(np.asarray(out["cycles"]), ref.cycles)
    assert np.asarray(out["halted"]).all()


@pytest.mark.slow
def test_pallas_differential_fuzz():
    """The standard differential harness (golden ≡ machine, event-skip on
    AND off) with the machine side running the pallas kernels — interpret
    mode pays per machine step, hence the slow tier."""
    for seed in range(6):
        sc = workloads.generate_scenario(seed, n_tenants=2 + seed % 3,
                                         kernels=workloads.CHEAP_MIX,
                                         max_tasks=4,
                                         heterogeneous_fus=seed % 3 == 0)
        hts.compare(sc.merged, schedulers=("hts_nospec", "hts_spec"),
                    fu_cost=sc.fu_cost, step_impl="pallas")


@pytest.mark.slow
def test_pallas_population_compare_fuzz():
    """Population differential: compare_population with step_impl="pallas"
    verifies the batched pallas machine against the golden loop in both
    event-skip modes."""
    progs = [workloads.generate_scenario(100 + s, n_tenants=2,
                                         kernels=workloads.CHEAP_MIX,
                                         max_tasks=4).merged
             for s in range(3)]
    hts.compare_population(progs, schedulers=("hts_spec",),
                           step_impl="pallas")


# ---------------------------------------------------------------------------
# completion write-back and TM drain: one window copy per write
# ---------------------------------------------------------------------------
SMALL = hts.HtsParams(max_tasks=32, cdb_entries=16)
#: two TM slots: the drain program's later speculations must drain
TM2 = hts.HtsParams(max_tasks=32, cdb_entries=16, tm_slots=2)


def _burst(consumers: int = 4) -> Program:
    """One producer feeds ``consumers`` iir tasks and three vector_add
    tasks; with four units a class and issue width 4, four units of the
    lane complete on one trip.  Every consumer writes a 4-word window of
    one region, each shifted by a word, so the windows overlap."""
    p = Program(f"burst{consumers}")
    frame = p.input(0x10, 4, "frame")
    pre = p.task("vector_dot", in_=frame, out=4, tid=0)
    size = max(consumers, 3) + 4
    shared = p.region(size, name="shared")
    shared.effect(list(range(100, 100 + size)))
    for i in range(consumers):
        p.task("iir", in_=pre, out=shared.sub(i, 4), tid=i + 1)
    for i in range(3):
        p.task("vector_add", in_=pre, out=shared.sub(i + 1, 4), tid=i)
    return p


#: speculated (not-taken) branches in the drain program, tasks on each
DRAIN_BRANCHES, DRAIN_SPEC_TASKS = 3, 3


def _drain() -> Program:
    """Speculated branches of three tasks each, run with two TM slots.
    The first speculation fills both slots and commits; they stay held
    by committed entries, so each later speculation drains both before
    its first two tasks dispatch (its third waits for the commit): two
    drains a speculation after the first."""
    p = Program("drain")
    frame = p.input(0x10, 4, "frame")
    thr = p.let(5, "thr")
    for b in range(DRAIN_BRANCHES):
        cond = p.region(1, name=f"cond{b}")
        cond.init(1)                        # not taken: speculation commits
        br = p.branch(on=cond, cond=">=", thr=thr, kind="mem")
        with br.not_taken():
            for i in range(DRAIN_SPEC_TASKS):
                out = p.region(4)
                out.effect([16 * b + 4 * i + j + 1 for j in range(4)])
                p.task("vector_dot", in_=frame, out=out, tid=i)
        with br.taken():
            p.task("vector_add", in_=frame, out=4, tid=0)
    p.task("vector_max", in_=frame, out=1, tid=0xF)
    return p


#: drains the program must make: two for each speculation after the first
DRAINS = 2 * (DRAIN_BRANCHES - 1)

#: case -> (program, run kwargs, most completions on one trip of the lane,
#: TM drains)
WRITEBACK = {
    "burst_overlap": (_burst, dict(n_fu=4, max_fu_per_class=4,
                                   params=SMALL), 4, 0),
    "loop_dependency_fu16": (programs.loop_dependency,
                             dict(n_fu=16, max_fu_per_class=16), 4, 0),
    "audio_bnt_fu16": (lambda: programs.audio_compression(8, False),
                       dict(n_fu=16, max_fu_per_class=16), 3, 0),
    "random_dependency_fu16": (programs.random_dependency,
                               dict(n_fu=16, max_fu_per_class=16), 2, 0),
    "drain": (_drain, dict(n_fu=2, max_fu_per_class=2, params=TM2), 1,
              DRAINS),
}


def _completions(raw) -> np.ndarray:
    comp = np.asarray(raw["tr_complete"])[1:int(raw["n_tasks"]) + 1]
    return comp[comp >= 0]


def _max_burst(raw) -> int:
    """Most tasks completing on one cycle — on one trip of the lane."""
    return max(collections.Counter(_completions(raw).tolist()).values())


def _golden(prog, kw):
    kw = {k: v for k, v in kw.items() if k != "max_fu_per_class"}
    return hts.run(prog, scheduler="hts_spec", backend="golden", **kw)


def _assert_matches_golden(res, gold):
    assert res.cycles == gold.cycles
    assert res.schedule_tuple() == gold.schedule_tuple()
    assert np.array_equal(np.asarray(res.raw["mem"]), gold.raw.mem)


def _assert_same_raw(ref, other, what):
    for k in ref:
        assert np.array_equal(np.asarray(ref[k]), np.asarray(other[k])), \
            f"{what} differ on {k!r}"


@pytest.mark.parametrize("case", sorted(WRITEBACK))
def test_writeback_bit_identical_single_lane(case):
    """xla ≡ xla_base ≡ pallas on every output array, and ≡ golden on
    cycles, schedule and memory, where several units of the lane
    complete on one trip or the TM drains.  ``mem_writes`` (the same
    under every impl) counts one window per completion plus one per
    drain, so on the drain case it shows the drains happened."""
    make, kw, burst, drains = WRITEBACK[case]
    prog = make()
    runs = {impl: hts.run(prog, scheduler="hts_spec", step_impl=impl, **kw)
            for impl in machine.STEP_IMPLS}
    ref = runs["xla"].raw
    assert _max_burst(ref) == burst
    assert int(ref["mem_writes"]) == len(_completions(ref)) + drains
    for impl in ("xla_base", "pallas"):
        _assert_same_raw(ref, runs[impl].raw, f"xla vs {impl}")
    gold = _golden(prog, kw)
    for res in runs.values():
        _assert_matches_golden(res, gold)


#: population -> (programs, run kwargs): lanes that complete several units
#: on one trip beside lanes that complete one, or drain
POPULATIONS = {
    "burst_drain": (lambda: [_burst(4), _drain(), _burst(2)],
                    dict(n_fu=4, max_fu_per_class=4, params=TM2)),
    "paper_fu16": (lambda: [programs.loop_dependency(),
                            programs.audio_compression(8, False),
                            programs.random_dependency()],
                   dict(n_fu=16, max_fu_per_class=16)),
}


@pytest.mark.parametrize("pop", sorted(POPULATIONS))
def test_writeback_bit_identical_population(pop):
    """The batched loop runs the most writes of any lane: lanes with
    fewer (or none) must come out as they would alone — xla ≡ xla_base ≡
    pallas on every output array, lane for lane, and each lane ≡ golden."""
    make, kw = POPULATIONS[pop]
    progs = make()
    runs = {impl: hts.run_many(progs, scheduler="hts_spec", step_impl=impl,
                               **kw)
            for impl in machine.STEP_IMPLS}
    ref = runs["xla"]
    assert max(_max_burst(ref[i].raw) for i in range(len(progs))) >= 3
    for impl in ("xla_base", "pallas"):
        _assert_same_raw(ref.raw, runs[impl].raw, f"xla vs {impl}")
    for i, prog in enumerate(progs):
        _assert_matches_golden(ref[i], _golden(prog, kw))


@pytest.mark.parametrize("budget", [1, 7])
@pytest.mark.parametrize("pop", sorted(POPULATIONS))
def test_writeback_slices_compose(pop, budget):
    """Resumable slices of ``budget`` steps, iterated to quiescence,
    collect the same carry as one run to completion, every array."""
    import jax
    import jax.numpy as jnp
    make, kw = POPULATIONS[pop]
    ref = hts.run_many(make(), scheduler="hts_spec", **kw)
    rm = api._population_slicer(ref._spec, ref._max_prog)
    args = [jnp.asarray(a) for a in ref._margs]
    carry = rm.init(*args)
    b = jnp.asarray(budget, jnp.int32)
    for _ in range(int(np.max(ref.steps)) // budget + 1):
        carry = rm.run_slice(carry, *args, b)
    _assert_same_raw(ref.raw, jax.device_get(rm.collect(carry)),
                     f"budget-{budget} slices vs one run")


# ---------------------------------------------------------------------------
# compile-key discipline
# ---------------------------------------------------------------------------
def test_default_step_impl_compile_bucket_unchanged():
    """The default path's compile key did not move: a default-constructed
    MachineSpec equals one with explicit step_impl="xla" (same lru
    bucket), explicit "xla" runs reuse the warm default bucket, and the
    other implementations compile into buckets of their own."""
    assert machine.MachineSpec() == machine.MachineSpec(step_impl="xla")
    sc, _ = _prep(0)
    # a max_cycles value no other test uses — this test owns its buckets
    # regardless of what the rest of the suite has already warmed
    mc = 4_999_991
    hts.run(sc.merged, n_fu=2, max_cycles=mc)        # warm default bucket
    before = machine._compiled.cache_info().misses
    hts.run(sc.merged, n_fu=2, max_cycles=mc, step_impl="xla")
    assert machine._compiled.cache_info().misses == before
    hts.run(sc.merged, n_fu=2, max_cycles=mc, step_impl="xla_base")
    assert machine._compiled.cache_info().misses == before + 1


def test_invalid_step_impl_raises():
    with pytest.raises(ValueError, match="step_impl"):
        machine.make_machine(machine.MachineSpec(), step_impl="triton")


def test_trip_cost_us_probe():
    """The profiling hook returns a positive per-trip figure on the jax
    backend and refuses on golden (no compiled machine to time)."""
    progs = [_prep(s)[0].merged for s in range(2)]
    r = hts.run_many(progs, scheduler="hts_spec")
    t = r.trip_cost_us(budget=16, reps=2)
    assert t > 0.0
    g = hts.run_many(progs, scheduler="hts_spec", backend="golden")
    with pytest.raises(ValueError, match="jax"):
        g.trip_cost_us()


def test_replicate_tiles_lanes():
    """batch.replicate widens a pack lane-for-lane: replica lanes produce
    the source lanes' cycles, so width sweeps vary only the width."""
    progs = [_prep(s)[0].merged for s in range(2)]
    pop = batch.pack_population(progs)
    wide = batch.replicate(pop, 5)
    assert len(wide) == 5
    ref = hts.run_many(pop, scheduler="hts_spec")
    r = hts.run_many(wide, scheduler="hts_spec")
    for i in range(5):
        assert int(r.cycles[i]) == int(ref.cycles[i % 2])
