"""Host time per call in the facade (``core/hts/api.py``), in the QoS
grid's cell.

``host_ms_per_call.batch``'s reading over the traced window's
``hts.run_many`` calls of the grid: the call's wall less the program's
own launch wall (``wall_us``), per call, in milliseconds: packing the
lanes and their policy tables (of which ``pack_ms_per_call.grid`` is the
pack), building the machine's arguments and the result, during which the
chip waits.  Moves ``scenarios_per_s``.
"""
from hts_bench import run

read = run.reader("host_ms_per_call.batch")
