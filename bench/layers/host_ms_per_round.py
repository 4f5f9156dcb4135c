"""host_ms_per_round: what the engine's host loop costs the chip per
traced round: the first device's idle time under the engine's stage
spans (``bench/spans.py`` ``idle_by_span``: every bucket but ``outside``
and ``between rounds``), summed over the window.  Host time spent
waiting on the device (the ring flush, the fold loop behind the wave)
leaves the device busy and does not count; device time spent waiting
on the host's dispatches, whichever stage they are in, does."""
import spans


def read(tr, ctx):
    if ctx["rounds"] < 1 or not tr["devices"]:
        return None
    sp = spans.of(tr)
    if not spans.total_ns(sp, "safl.run"):
        return None
    return spans.engine_idle_ns(tr, sp) / 1e6 / ctx["rounds"]
