"""Lane occupancy of the serving layer (``core/hts/serve.py``).

``ServeReport.per_bucket[*].occupancy`` (the server's own measure of real
lanes per slice) over the traced window's launches, weighted by launches.
Moves ``ttr_p95_ms``.
"""


def read(ctx):
    c = ctx["counters"]
    if not c.get("launches"):
        return None
    return 100.0 * c["occupancy_sum"] / c["launches"]
