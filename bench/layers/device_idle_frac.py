"""device_idle_frac: share of the traced window in which no operation ran
on the device (1 - union of the ``XLA Ops`` intervals over the window),
averaged over the cell's chips."""
from layers_common import events, union_ns, used_devices, OPS


def read(tr, ctx):
    devs = used_devices(tr, ctx["chips"])
    lo, hi = tr["window"]
    if not devs or hi <= lo:
        return None
    busy = [union_ns((s, e) for _n, s, e in events(d, OPS)) for d in devs]
    if not any(busy):
        return None
    return 1.0 - sum(busy) / len(busy) / (hi - lo)
