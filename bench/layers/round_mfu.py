"""round_mfu: model FLOPs of the traced rounds (training forward and
backward of every local sample, plus the evaluation forward passes,
from shapes: ``bench/work.py``) over the traced window times the chips'
bf16 peak, in percent."""


def read(tr, ctx):
    if ctx["rounds"] < 1 or ctx["window_s"] <= 0:
        return None
    peak = ctx["window_s"] * ctx["chips"] * ctx["peak"]["bf16_flops"]
    return 100.0 * ctx["rounds"] * ctx["round_flops"] / peak
