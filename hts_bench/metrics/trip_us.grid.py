"""Time per machine trip (``core/hts/machine.py``), in the QoS grid's
cell.

``trip_us.batch``'s reading: the program's own launch wall
(``PopulationResult.wall_us``) over the while-loop trips of the traced
window's ``run_many`` calls (each call's slowest lane's steps), here with
the arbiter's weights, quotas and RS caps binding.  Moves
``scenarios_per_s``.
"""
from hts_bench import run

read = run.reader("trip_us.batch")
