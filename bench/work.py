"""The work a round needs, from shapes: model FLOPs and the server fold's
HBM bytes.  These are what the algorithm needs, whatever implements it.
"""
from __future__ import annotations


def round_flops(cfg: dict, ref, traffic: dict) -> float:
    """Model FLOPs of one aggregation round: the configuration's training
    step (``flops_train``) for every local sample of the round's ``k``
    uploads, plus one forward pass per evaluation sample.  Recomputed
    operations do not count."""
    eng, pop = traffic["engine"], traffic["population"]
    train = (ref.flops_train(cfg) * int(eng["k"])
             * int(pop["samples_per_client"]) * int(eng["local_epochs"]))
    return train + ref.flops_forward(cfg) * int(traffic["eval_samples"])


def row_length(cfg: dict, ref) -> int:
    """Elements of the uploaded row: the trained part of ``init``'s
    weights (``params``; not the state, not a frozen part), from shapes
    alone (nothing is allocated)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: ref.init(cfg, k), key)[0]
    return sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(params))


def fold_bytes(d: int, wire: str, qblock: int) -> int:
    """HBM bytes one streaming fold must move: read and write the f32
    accumulator row, and read the upload in its wire format (f32 row, or
    int8 codes plus one f32 scale per ``qblock`` values on the padded
    grid)."""
    if wire == "f32":
        return 3 * 4 * d
    if wire == "q8":
        dq = -(-d // qblock) * qblock
        return 2 * 4 * dq + dq + 4 * (dq // qblock)
    raise ValueError(f"no fold byte count for wire {wire!r}")
