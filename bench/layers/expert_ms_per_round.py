"""expert_ms_per_round: device time of the held experts' grouped matrix
products per traced round, on the slowest of the cell's chips.

``models/moe.py``'s dropless expert layer runs every held expert's rows
through ``jax.lax.ragged_dot``, which the TPU compiler lowers to one
kernel per product, an operation named ``%ragged-dot-none[.N]
custom-call`` on the ``XLA Ops`` line (names from the compiled HLO of
the Moonlight wave for a v5e); its group bookkeeping,
``%ragged-dot-metadata``, is not a product and is left out.  Forward
products of training and evaluation count, and so do the backward's
recomputed and transposed ones."""
import re

from layers_common import OPS, events, used_devices

#: the grouped-product kernels (not their metadata op)
KERNEL = re.compile(r"%ragged-dot-(?!metadata)[\w\-]+(\.\d+)? custom-call")


def expert_ns(dev) -> int:
    return sum(e - s for n, s, e in events(dev, OPS) if KERNEL.fullmatch(n))


def read(tr, ctx):
    if ctx["rounds"] < 1:
        return None
    t = max((expert_ns(d) for d in used_devices(tr, ctx["chips"])),
            default=0)
    return t / 1e6 / ctx["rounds"] if t else None
