"""Queue wait in the serving layer (``core/hts/serve.py``).

``ServeReport.per_bucket[*].mean_wait`` (from submit to the slice that
first ran the request) over the traced window's requests, weighted by
requests, in milliseconds.  Moves ``ttr_p50_ms``.
"""


def read(ctx):
    c = ctx["counters"]
    if not c.get("requests"):
        return None
    return 1e3 * c["wait_sum_s"] / c["requests"]
