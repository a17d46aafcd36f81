"""Host time per batch call in the facade (``core/hts/api.py``).

Over the traced window's ``hts.run_many`` or ``hts.sweep`` calls: the
call's wall less the part the program timed around its device launches
(``wall_us``), per call, in milliseconds: packing the scenarios, building
the machine's arguments and the result, during which the chip waits.
Moves ``scenarios_per_s``.
"""


def read(ctx):
    c = ctx["counters"]
    if not c.get("calls"):
        return None
    return 1e3 * (c["call_s"] - c["launch_s"]) / c["calls"]
