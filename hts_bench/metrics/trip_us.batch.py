"""Time per machine trip (``core/hts/machine.py``).

The program's own wall around its device launches (``PopulationResult.
wall_us``: arguments to the device, the machine's run, results back) over
the while-loop trips of the traced window's ``run_many`` calls (each
call's slowest lane's steps).  A trip is one step of every lane, so this
is the cost of the machine step at the call's width; a call runs for
seconds, so the host clock's error does not show.  Moves
``scenarios_per_s``.
"""


def read(ctx):
    c = ctx["counters"]
    if not c.get("trips"):
        return None
    return 1e6 * c["launch_s"] / c["trips"]
