"""fold_roofline: the streaming fold kernels' share of their HBM roofline,
in percent.  Bytes per fold from shapes (``bench/work.py``: the f32
accumulator row read and written, plus the upload in its wire format)
times the folds in the traced window, over the summed device time of the
fold kernel's operations times the chip's HBM bandwidth.  The fold does
two FLOPs per accumulator element, so bandwidth bounds it."""
import re

from layers_common import events, used_devices, OPS

#: the Pallas fold kernels of ``kernels/safl_agg.py`` (``safl_fold``,
#: ``safl_fold_q8``): the custom call of ``FlatServer``'s ``_fold`` program
KERNEL = re.compile(r"%_fold(\.\d+)? custom-call")


def read(tr, ctx):
    devs = used_devices(tr, ctx["chips"])
    t = sum(e - s for d in devs for n, s, e in events(d, OPS)
            if KERNEL.fullmatch(n))
    if not t or ctx["rounds"] < 1:
        return None
    folds = ctx["rounds"] * ctx["uploads_per_round"]
    need = folds * ctx["fold_bytes"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * need / (t / 1e9)
