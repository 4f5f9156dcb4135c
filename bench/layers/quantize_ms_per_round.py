"""quantize_ms_per_round: device time of the q8 wire's quantize program
per traced round, on the slowest of the cell's chips: ``core/flatbuf.py``
``PytreeCodec.quantize_rows``, which adds each lane's error-feedback
residual to its upload, quantises the rows blockwise to int8 and returns
the new residuals, one program per wave.

It is not the whole wire: the stack of the wave's residuals before it
and the per-row slices of codes and residuals after it are programs of
their own with generic names, counted in ``launches_per_round`` and
``host_ms_per_round``.  A change that fuses them into this program
raises this number while the wire gets cheaper; those two then fall."""
from layers_common import module_time_ns, used_devices

PROGRAMS = r"jit__quantize"


def read(tr, ctx):
    if ctx["rounds"] < 1:
        return None
    t = max((module_time_ns(d, PROGRAMS)
             for d in used_devices(tr, ctx["chips"])), default=0)
    return t / 1e6 / ctx["rounds"] if t else None
