"""The closed-loop batch driver, with the program's own pack time among
its counters.

Everything is :mod:`hts_bench.drivers.batch`'s; ``counters()`` adds
``pack_s``, the window's Σ ``PopulationResult.pack_us`` (the ``hts.pack``
span: ``run_many`` packing the lanes' programs and policy tables on the
host, before its launch).  A program whose results carry no ``pack_us``
gives no ``pack_s``, and its reader then finds nothing to read.
"""
from __future__ import annotations

from hts_bench.drivers import batch


class Driver(batch.Driver):
    def counters(self) -> dict:
        out = super().counters()
        packs = [getattr(res, "pack_us", None) for _, res in self.calls]
        if packs and None not in packs:
            out["pack_s"] = 1e-6 * sum(packs)
        return out
