"""A run's comparison catches a broken timed path, and its control.

Each test shrinks the cell's traffic to the size its traffic file gives
under ``test_sizes`` (one the CPU holds), skips the look for a chip and
drives the rest of a run (set-up, window, check) with a fault planted
where the answers are produced: ``correct`` must come out false.  The
unbroken run must come out true.
"""
import argparse
import dataclasses
import json

import numpy as np
import pytest

from hts_bench import control, run

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cpu_device(chips):
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


@pytest.fixture
def small(monkeypatch):
    """The cell's traffic at a size the CPU holds, and no look for a
    chip."""
    load = run.load_cell
    monkeypatch.setattr(run, "device_info", _cpu_device)

    def shrunk(name, root=run.ROOT):
        spec = load(name, root)
        spec["traffic"].update(spec["traffic"]["test_sizes"])
        return spec

    monkeypatch.setattr(run, "load_cell", shrunk)
    return shrunk


def _run(cell, seed=11):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=1.0,
                              trace=0)
    return run.run(args)


# -- faults, each planted where the answers are produced -------------------
def _alter(res):
    """Every answer one cycle off."""
    from repro.core.hts import api
    if isinstance(res, api.SweepResult):
        return dataclasses.replace(
            res, cycles={k: v + 1 for k, v in res.cycles.items()})
    raw = dict(res.raw, cycles=res.raw["cycles"] + 1)
    return dataclasses.replace(res, cycles=raw["cycles"], raw=raw)


def _halve(res):
    """The second half of the batch left out: its rows copied from the
    first half."""
    from repro.core.hts import api

    def fold(a):
        a = np.array(a)
        h = len(a) // 2
        a[h:2 * h] = a[:h]
        return a

    if isinstance(res, api.SweepResult):
        return dataclasses.replace(
            res, cycles={k: fold(v) for k, v in res.cycles.items()})
    raw = {k: fold(v) for k, v in res.raw.items()}
    return dataclasses.replace(res, cycles=raw["cycles"], raw=raw,
                               halted=fold(res.halted))


def _stuck(res):
    """A step that returns its state unchanged: no lane ever halts."""
    from repro.core.hts import api
    if isinstance(res, api.SweepResult):
        raise api.SimulationError("no point halted")
    raw = dict(res.raw, halted=np.zeros_like(res.raw["halted"]),
               cycles=np.zeros_like(res.raw["cycles"]))
    return dataclasses.replace(res, cycles=raw["cycles"], raw=raw,
                               halted=raw["halted"])


def _plant_batch(monkeypatch, fault):
    from repro.core import hts
    for entry in ("run_many", "sweep"):
        real = getattr(hts, entry)
        monkeypatch.setattr(hts, entry,
                            lambda *a, _real=real, **k: fault(_real(*a, **k)))


def _plant_serve(monkeypatch, fault):
    """The server resolves a request's future with a broken answer."""
    from repro.core import hts
    real = hts.Server._resolve
    count = {"n": 0}

    def resolve(self, key, req, result, t_filled, t_done):
        count["n"] += 1
        if fault == "alter" and result is not None:
            result = dataclasses.replace(result, cycles=result.cycles + 1)
        elif fault == "stuck" or (fault == "halve" and count["n"] % 2):
            result = None
        real(self, key, req, result, t_filled, t_done)

    monkeypatch.setattr(hts.Server, "_resolve", resolve)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small, cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["alter", "halve", "stuck"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(small, monkeypatch, cell, fault):
    from repro.core.hts import api
    if small(cell)["traffic"]["driver"] == "serve_open":
        _plant_serve(monkeypatch, fault)
    else:
        _plant_batch(monkeypatch, {"alter": _alter, "halve": _halve,
                                   "stuck": _stuck}[fault])
    try:
        out = _run(cell)
    except api.SimulationError:
        return                       # the run ends with no result at all
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_comparison(small, cell):
    spec = small(cell)
    readings = [control.reading(spec, seed, seconds=1.0)
                for seed in (1, 2, 3)]
    assert all(r["differing"] > 0 for r in readings), readings


# -- per-lane scheduling policies ------------------------------------------
def _grid_traffic(seed, t=None, seconds=None):
    """The QoS policy grid at a size the CPU holds: the 8 instances of its
    2-flood mix under weights 0 and 8, in an order set by the seed."""
    from hts_bench import gen
    pairs = gen.qos_grid(mixes=(2,), weights=(0, 8))
    order = np.random.default_rng([seed, 0]).permutation(len(pairs))
    return gen.Traffic([pairs[k][0] for k in order],
                       [list(range(len(pairs)))],
                       policies=[pairs[k][1] for k in order])


def _grid_spec():
    """The grid's cell as the next configuration would state it: the
    generated mix's machine, with the policy control."""
    cfg = json.loads((run.BENCH / "configs" / "generated_mix.json")
                     .read_text())
    return {"config": dict(cfg, control="policy"),
            "traffic": {"driver": "batch", "entry": "run_many",
                        "capacities": {"max_tasks": 192, "cdb_entries": 64},
                        "max_prog": 64, "check_lanes": 8}}


@pytest.mark.parametrize("mode", ["policies", "none", "dropped"])
def test_policies_reach_run_many(monkeypatch, mode):
    """The batch driver passes each lane's policy to ``run_many`` (and no
    ``policy=`` at all for traffic without policies), and its check holds
    each lane to its own policy: a program that drops them is caught."""
    from hts_bench import common, gen
    from hts_bench.drivers import batch
    from repro.core import hts
    traffic = _grid_traffic(5)
    if mode == "none":
        traffic.policies = None
    monkeypatch.setattr(gen, "generate", lambda *a: traffic)
    seen = []
    real = hts.run_many

    def recording(*a, **k):
        seen.append(k.get("policy", "absent"))
        if mode == "dropped":
            k.pop("policy", None)
        return real(*a, **k)

    monkeypatch.setattr(hts, "run_many", recording)
    spec = _grid_spec()
    drv = batch.Driver(spec["config"], spec["traffic"], 5, 0.0,
                       common.Spans())
    drv.setup()
    drv.window(0.0)
    checks = {c.name: c.value for c in drv.check()}
    assert seen[0] == "absent"                  # the warm-up
    if mode == "none":
        assert seen[1:] == ["absent"]
    else:
        assert seen[1:] == [[hts.SchedPolicy.of(**d)
                             for d in traffic.policies]]
    bad = checks["lanes_differing_from_reference"]
    assert (bad > 0) == (mode == "dropped"), checks


def test_policy_control_fails_the_comparison(monkeypatch):
    from hts_bench import gen
    monkeypatch.setattr(gen, "generate", _grid_traffic)
    spec = _grid_spec()
    readings = [control.reading(spec, seed, seconds=1.0)
                for seed in (1, 2, 3)]
    assert all(r["differing"] > 0 for r in readings), readings
