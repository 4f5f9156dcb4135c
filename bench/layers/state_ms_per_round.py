"""state_ms_per_round: host time per traced round spent on model state:
assembling each wave's per-client rows and states (``safl.gather``), the
per-client refresh after the wave (``safl.refresh``) and the global
non-trainable state update (``safl.state``), from the engine's spans
(``bench/spans.py``)."""
import spans

STAGES = ("safl.gather", "safl.refresh", "safl.state")


def read(tr, ctx):
    if ctx["rounds"] < 1:
        return None
    t = spans.total_ns(spans.of(tr), *STAGES)
    return t / 1e6 / ctx["rounds"] if t else None
