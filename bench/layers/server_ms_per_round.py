"""server_ms_per_round: device time of the server's programs per traced
round: the streaming fold of every upload and the finalize (server step
and update norm) of ``core/aggregation.py`` ``FlatServer``, on the
slowest of the cell's chips."""
from layers_common import module_time_ns, used_devices

PROGRAMS = r"jit__fold|jit__finalize"


def read(tr, ctx):
    if ctx["rounds"] < 1:
        return None
    t = max((module_time_ns(d, PROGRAMS)
             for d in used_devices(tr, ctx["chips"])), default=0)
    return t / 1e6 / ctx["rounds"] if t else None
