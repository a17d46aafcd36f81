"""Lane occupancy of the batching layer (``core/hts/batch.py``).

The machine's own step counters: over the traced window's calls, the steps
every lane ran over lanes times the call's while-loop trips (its slowest
lane's steps).  A call drains at its slowest lane, so this is the share of
the batch's lane-trips that did work.  Moves ``scenarios_per_s``.
"""


def read(ctx):
    c = ctx["counters"]
    if not c.get("lane_trips"):
        return None
    return 100.0 * c["lane_steps"] / c["lane_trips"]
