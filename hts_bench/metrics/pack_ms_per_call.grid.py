"""Pack time per call in the batching layer (``core/hts/batch.py``).

Over the traced window's ``hts.run_many`` calls: the program's own
``hts.pack`` span (``PopulationResult.pack_us``: ``pack_population``
padding each lane's program into the shared table and building its policy
tables), per call, in milliseconds.  The chip waits through it.  Moves
``scenarios_per_s``.
"""


def read(ctx):
    c = ctx["counters"]
    if not c.get("calls") or c.get("pack_s") is None:
        return None
    return 1e3 * c["pack_s"] / c["calls"]
