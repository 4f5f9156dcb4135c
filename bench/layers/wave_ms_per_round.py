"""wave_ms_per_round: device time of the client wave programs
(``core/client.py``'s ``round_fn``) per traced round, on the slowest of
the cell's chips."""
from layers_common import module_time_ns, used_devices

PROGRAM = r"jit_round_fn"


def read(tr, ctx):
    if ctx["rounds"] < 1:
        return None
    t = max((module_time_ns(d, PROGRAM)
             for d in used_devices(tr, ctx["chips"])), default=0)
    return t / 1e6 / ctx["rounds"] if t else None
