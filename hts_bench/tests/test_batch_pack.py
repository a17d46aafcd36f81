"""The pack-timing batch driver: its counters add the program's pack time
where the results carry it, and leave it out where they do not, so that
its reader finds nothing to read on a program without ``pack_us``."""
import dataclasses

import numpy as np
import pytest

from hts_bench import run
from hts_bench.drivers import batch, batch_pack


@dataclasses.dataclass
class _Result:
    """What ``counters()`` reads of a ``run_many`` result."""
    wall_us: float
    steps: np.ndarray


@dataclasses.dataclass
class _Packed(_Result):
    pack_us: float


def _driver(results):
    drv = batch_pack.Driver({}, {"entry": "run_many"}, 1, 0.0, None)
    drv.calls = list(enumerate(results))
    drv.call_s = 0.5
    return drv


@pytest.mark.parametrize("with_pack", [True, False])
def test_counters_add_pack_time_where_results_carry_it(with_pack):
    steps = np.array([3, 5])
    results = ([_Packed(1000.0, steps, 250.0), _Packed(3000.0, steps, 750.0)]
               if with_pack else
               [_Result(1000.0, steps), _Result(3000.0, steps)])
    drv = _driver(results)
    got = drv.counters()
    want = batch.Driver.counters(drv)
    read = run.reader("pack_ms_per_call.grid")
    if with_pack:
        assert got == dict(want, pack_s=pytest.approx(1e-3))
        assert read({"counters": got}) == pytest.approx(0.5)
    else:
        assert got == want
        assert read({"counters": got}) is None
    assert got["trips"] == 10 and got["launch_s"] == pytest.approx(4e-3)
    for name in ("host_ms_per_call", "trip_us"):
        ctx = {"counters": got}
        assert run.reader(f"{name}.grid")(ctx) == \
            run.reader(f"{name}.batch")(ctx) is not None


def test_a_sound_result_carries_pack_time():
    """``run_many``'s own results give the counter a positive pack time."""
    from repro.core import hts
    p = hts.Program("one")
    p.task("vector_dot", in_=p.input(0x10, 4), out=4)
    drv = _driver([hts.run_many([p.build()] * 2, n_fu=2)])
    assert drv.counters()["pack_s"] > 0.0
