"""launches_per_round: XLA programs executed on the first device per
traced round (events on its ``XLA Modules`` line)."""
from layers_common import events, MODULES


def read(tr, ctx):
    if ctx["rounds"] < 1 or not tr["devices"]:
        return None
    n = len(events(tr["devices"][0], MODULES))
    return n / ctx["rounds"] if n else None
