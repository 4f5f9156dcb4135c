"""Fused SAFL aggregation kernels (pl.pallas_call + BlockSpec VMEM tiling).

The paper's server round is a K-way weighted reduction over flat update
vectors (K = buffer size, D = model size).  Done naively this is K+2 HBM
passes (read each update, read params, write params) plus a K x D staging
copy when the updates arrive as pytrees; the fused kernels do one streaming
pass: each grid step loads a (K, BLOCK_D) update tile + (BLOCK_D,) state
tiles into VMEM, reduces over K in registers, applies the server step,
writes the new state tiles.

Kernels:
  * ``safl_aggregate`` — weighted mean (+ optional fused (1+tau)^-alpha
    staleness discount) with an optional fused SGD server step.  Covers
    fedsgd (unit weights), fedavg (data-size weights), fedbuff
    (staleness-discounted gradient mean) and — via ``mode="mix"`` —
    fedasync: K sequential per-update mixes p <- (1-a_i) p + a_i w_i
    fold into one unnormalized linear combination
    (1 - sum(c)) p + c @ u with c_i = a_i prod_{j>i}(1-a_j), so the
    per-update pytree path becomes one fused buffered pass.  ``mode="sum"``
    is the shard-aware grid: the *unnormalized* weighted row sum w @ u
    with no server step — the per-shard partial each device computes when
    the (K, D) buffer is sharded over the mesh "pod" axis
    (repro.sharding.flat.podwise_sums runs it per shard and folds the
    partials with one psum; the caller then applies the server step to the
    reduced mean).
  * ``sdga_aggregate`` — the full SDGA server round in one pass: staleness
    discount, weighted mean, server momentum, SGD step and EMA anchor, with
    the new params / momentum / EMA emitted as three fused outputs.
  * ``safl_aggregate_q8`` / ``sdga_aggregate_q8`` — the same rounds over the
    *quantized* flat channel: updates arrive as int8 (K, D) rows plus one
    f32 absmax scale per QBLOCK lanes (:mod:`repro.kernels.quantize` wire
    format), and each grid step fuses blockwise dequantize into the
    reduction — the K x D read is 4x fewer HBM bytes than the f32 buffer,
    which is exactly the memory-bound large-D regime.  ``*_q4`` do the
    same over packed two-nibbles-per-byte rows.

The top-k sparse wire has no kernel: its reduction scatters, Pallas TPU
has no scatter lowering, and a tile-rebased scatter would stage the whole
(K, nk) payload in VMEM at every grid step.  The server runs the XLA
scatter of :mod:`repro.kernels.ref` for it on every backend.

TPU sizing: BLOCK_D = 4096 lanes x K<=64 buffered updates x 4B = 1 MiB of
VMEM per f32 tile — inside the 16 MiB scoped v5e VMEM with double
buffering.  The quantized kernels read their scales *transposed*, as
(Dq/qblock, K) with a (BLOCK_D/qblock, K) block: Mosaic needs the block's
second-minor dim to be a multiple of 8 and its minor dim a multiple of
128 or the full dim, and K is the full dim.  So BLOCK_D / qblock must be
a multiple of 8 (8 x 512 = 4096 lanes at the default qblock).
:func:`check_tiling` states the rule; the compile-only tests
(``tests/test_tpu_compile.py``) hold every kernel to it at ResNet-18
width.

Backend selection (:func:`default_backend`): compiled Pallas on TPU,
interpret-mode Pallas or the jnp oracle (:mod:`repro.kernels.ref`) on CPU —
override with ``REPRO_AGG_BACKEND=pallas|pallas_interpret|xla``.
Validated on CPU in interpret mode against repro.kernels.ref oracles.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quantize import BLOCK as QBLOCK

BLOCK_D = 4096

# discount: how the (K,) weight-input vector becomes reduction weights
#   "none" — use as-is (unit / data-size weights)
#   "poly" — treat as staleness tau, apply (1 + tau)^(-alpha)  (Fig. 4)
_DISCOUNTS = ("none", "poly")


def default_backend() -> str:
    """Platform auto-detect: compiled Pallas on TPU, jnp oracle elsewhere
    (interpret-mode Pallas is a functional validator, not a fast path)."""
    env = os.environ.get("REPRO_AGG_BACKEND")
    if env:
        assert env in ("pallas", "pallas_interpret", "xla"), env
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def edge_partial_reduce(val: jax.Array, *, pod_size: int,
                        pod_axis: str = "pod",
                        edge_axis: str = "edge") -> jax.Array:
    """Hierarchical reduction of per-shard ``mode="sum"`` partials on a
    2-D (edge, pod) mesh: callable only inside ``shard_map``.

    Stage 1 — intra-edge tree reduce: log2(P) recursive-doubling rounds
    of ``ppermute`` over the pod sub-axis (round r adds the partner
    ``i ^ 2**r``), so after the last round every pod shard of an edge
    group holds the full *edge partial*.  These hops stay on the fast
    intra-edge links.  Stage 2 — ONE ``psum`` of the E edge partials over
    the edge axis: the only traffic that crosses the slow edge boundary,
    E operands instead of the E*P a flat global psum exchanges (the ~P x
    cross-edge traffic reduction the hierarchy buys).

    The XOR pairing is deterministic, so the host oracle
    (:func:`repro.kernels.ref.xor_tree_sum_ref`) reproduces the addition
    order bitwise.  ``pod_size`` must be a power of two (falls back to a
    plain pod-axis psum otherwise — same value, unspecified order).
    """
    if pod_size > 1:
        if pod_size & (pod_size - 1) == 0:
            shift = 1
            while shift < pod_size:
                perm = [(i, i ^ shift) for i in range(pod_size)]
                val = val + jax.lax.ppermute(val, pod_axis, perm)
                shift *= 2
        else:  # pragma: no cover - configs validate pow2 pod groups
            val = jax.lax.psum(val, pod_axis)
    return jax.lax.psum(val, edge_axis)


def _weights(w, alpha: float, discount: str):
    w = w.astype(jnp.float32)
    if discount == "poly":
        w = jnp.power(1.0 + w, -alpha)
    return w


def _matvec(w, u):
    """(K,) weights x (K, BD) tile -> (BD,) weighted row sum.  A
    broadcast-multiply + sublane reduce: Mosaic has no lowering for the
    1-D ``einsum("k,kd->d")`` dot."""
    return jnp.sum(w[:, None] * u, axis=0)


def _agg_kernel(w_ref, u_ref, p_ref, o_ref, *, server_lr: float,
                mode: str, alpha: float, discount: str):
    """One (K, BLOCK_D) tile: o = p - lr * (w @ u)/sum(w)  (fedsgd),
    o = (w @ u)/sum(w)  (avg), or the *unnormalized* fedasync fold
    o = (1 - sum(w)) * p + w @ u  (mix) — K sequential per-update mixes
    p <- (1-a_i) p + a_i u_i collapse into this one linear combination
    when w_i = a_i * prod_{j>i} (1 - a_j)."""
    w = _weights(w_ref[...], alpha, discount)  # (K,)
    u = u_ref[...].astype(jnp.float32)  # (K, BLOCK_D)
    if mode == "mix":
        p = p_ref[...].astype(jnp.float32)
        g = _matvec(w, u)
        o_ref[...] = ((1.0 - jnp.sum(w)) * p + g).astype(o_ref.dtype)
        return
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    g = _matvec(w, u) / wsum
    if mode == "fedsgd":
        p = p_ref[...].astype(jnp.float32)
        o_ref[...] = (p - server_lr * g).astype(o_ref.dtype)
    else:
        o_ref[...] = g.astype(o_ref.dtype)


def safl_aggregate(updates: jax.Array, weights: jax.Array,
                   params: jax.Array | None = None,
                   server_lr: float = 1.0, mode: str = "fedsgd",
                   block_d: int = BLOCK_D,
                   interpret: bool = True,
                   alpha: float = 0.5,
                   discount: str = "none") -> jax.Array:
    """updates (K, D), weights (K,), params (D,) [fedsgd / mix] -> (D,).

    ``discount="poly"`` reads ``weights`` as staleness and applies the
    (1+tau)^(-alpha) discount inside the kernel (fedbuff's weighting).
    ``mode="mix"`` is the fedasync fold: weights are precomputed mix
    coefficients (:func:`repro.core.aggregation.fedasync_coefficients`)
    and o = (1 - sum(w)) * params + w @ updates, unnormalized.
    ``mode="sum"`` is the per-shard partial: the unnormalized weighted
    row sum w @ updates (no params, no normalization, no server step) —
    what each device reduces locally under the mesh "pod" sharding before
    the one psum.  D is padded to a multiple of ``block_d`` internally.
    """
    assert discount in _DISCOUNTS
    K, D = updates.shape
    pad = (-D) % block_d
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
        if params is not None:
            params = jnp.pad(params, (0, pad))
    Dp = D + pad
    grid = (Dp // block_d,)
    out_dtype = params.dtype if params is not None else jnp.float32
    if mode in ("fedsgd", "mix"):
        assert params is not None
        args = (weights, updates, params)
        in_specs = [
            pl.BlockSpec((K,), lambda i: (0,)),
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
            pl.BlockSpec((block_d,), lambda i: (i,)),
        ]
    else:
        args = (weights, updates)
        in_specs = [
            pl.BlockSpec((K,), lambda i: (0,)),
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
        ]
    kern = functools.partial(
        _agg_kernel if mode in ("fedsgd", "mix") else _avg_kernel,
        server_lr=server_lr, mode=mode, alpha=alpha, discount=discount)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_d,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Dp,), out_dtype),
        interpret=interpret,
    )(*args)
    return out[:D]


def _avg_kernel(w_ref, u_ref, o_ref, *, server_lr: float, mode: str,
                alpha: float, discount: str):
    del server_lr
    w = _weights(w_ref[...], alpha, discount)
    u = u_ref[...].astype(jnp.float32)
    g = _matvec(w, u)
    if mode != "sum":  # "avg" normalizes; "sum" is the per-shard partial
        g = g / jnp.maximum(jnp.sum(w), 1e-12)
    o_ref[...] = g.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# streaming accumulate-on-arrival: fold one upload into the running sum
# ---------------------------------------------------------------------------


def _fold_kernel(s_ref, a_ref, v_ref, o_ref):
    """One (BLOCK_D,) tile of the streaming fold o = beta*a + w*v.

    s_ref is the (2,) scalar pair [beta, w]: beta decays the existing
    accumulator (1.0 for the sum modes, 1 - a_i for the fedasync
    sequential mix), w is the arriving upload's final ingest weight
    (discount-at-ingest: staleness discount / data size / policy score
    are folded before dispatch)."""
    o_ref[...] = (s_ref[0] * a_ref[...].astype(jnp.float32)
                  + s_ref[1] * v_ref[...].astype(jnp.float32))


def safl_fold(acc: jax.Array, vec: jax.Array, w, beta=1.0,
              block_d: int = BLOCK_D, interpret: bool = True) -> jax.Array:
    """Streaming fold: acc (D,) f32 running partial sum, vec (D,) one
    arriving upload -> beta*acc + w*vec, one fused pass (oracle
    :func:`repro.kernels.ref.fold_ref`).  The O(1)-memory replacement
    for buffering a (K, D) row per client: K chained folds equal the
    ``mode="sum"`` reduction bitwise on XLA CPU."""
    D = acc.shape[0]
    pad = (-D) % block_d
    if pad:
        acc = jnp.pad(acc, (0, pad))
        vec = jnp.pad(vec, (0, pad))
    Dp = D + pad
    sw = jnp.stack([jnp.asarray(beta, jnp.float32),
                    jnp.asarray(w, jnp.float32)])
    vec_spec = pl.BlockSpec((block_d,), lambda i: (i,))
    out = pl.pallas_call(
        _fold_kernel,
        grid=(Dp // block_d,),
        in_specs=[pl.BlockSpec((2,), lambda i: (0,)), vec_spec, vec_spec],
        out_specs=vec_spec,
        out_shape=jax.ShapeDtypeStruct((Dp,), jnp.float32),
        interpret=interpret,
    )(sw, acc, vec)
    return out[:D]


def _fold_q8_kernel(s_ref, a_ref, q_ref, sc_ref, o_ref, *, qblock: int):
    """Streaming fold of one quantized row tile: blockwise dequantize the
    (1, BLOCK_D) int8 slice in VMEM, then o = beta*a + w*u.  The row runs
    as a 2-D (1, D) array: Mosaic cannot split a rank-1 tile into
    (BLOCK_D/qblock, qblock) scale groups."""
    u = _dequant_tile(q_ref[...], sc_ref[...], qblock)
    o_ref[...] = s_ref[0] * a_ref[...].astype(jnp.float32) + s_ref[1] * u


def safl_fold_q8(acc: jax.Array, q_row: jax.Array, scales_row: jax.Array,
                 w, beta=1.0, qblock: int = QBLOCK,
                 block_d: int = BLOCK_D, interpret: bool = True) -> jax.Array:
    """Quantized-channel streaming fold: acc (Dq,) f32, q_row (Dq,) int8,
    scales_row (Dq/qblock,) f32 -> beta*acc + w*dequant(q_row), with the
    blockwise dequantize fused into the single pass (oracle
    :func:`repro.kernels.ref.fold_q8_ref`)."""
    Dq = acc.shape[0]
    assert q_row.shape == (Dq,) and block_d % qblock == 0
    pad = (-Dq) % block_d
    if pad:
        acc = jnp.pad(acc, (0, pad))
        q_row = jnp.pad(q_row, (0, pad))
        scales_row = jnp.pad(scales_row, (0, pad // qblock))
    Dp = Dq + pad
    sw = jnp.stack([jnp.asarray(beta, jnp.float32),
                    jnp.asarray(w, jnp.float32)])
    row_spec = pl.BlockSpec((1, block_d), lambda i: (0, i))
    out = pl.pallas_call(
        functools.partial(_fold_q8_kernel, qblock=qblock),
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,)),
            row_spec,
            row_spec,
            _col_scale_spec(block_d, qblock),
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        interpret=interpret,
    )(sw, acc[None], q_row[None], scales_row[:, None])
    return out[0, :Dq]


# ---------------------------------------------------------------------------
# SDGA: staleness discount + momentum + SGD step + EMA anchor, one pass
# ---------------------------------------------------------------------------


def _sdga_kernel(tau_ref, u_ref, p_ref, m_ref, e_ref,
                 op_ref, om_ref, oe_ref, *, server_lr: float, alpha: float,
                 momentum: float, ema_anchor: float, ema_decay: float,
                 discount: str):
    """One (K, BLOCK_D) tile of the full SDGA server round:

        w   = (1 + tau)^(-alpha)     [discount="poly"; "none" reads the
                                      weight input as final weights]
        g   = (w @ u) / sum(w)
        m'  = momentum * m + g
        p'  = p - lr * m' + ema_anchor * (e - p)
        e'  = ema_decay * e + (1 - ema_decay) * p'
    """
    w = _weights(tau_ref[...], alpha, discount)
    u = u_ref[...].astype(jnp.float32)
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    g = _matvec(w, u) / wsum
    m_new = momentum * m_ref[...].astype(jnp.float32) + g
    p = p_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    p_new = p - server_lr * m_new + ema_anchor * (e - p)
    e_new = ema_decay * e + (1.0 - ema_decay) * p_new
    op_ref[...] = p_new.astype(op_ref.dtype)
    om_ref[...] = m_new.astype(om_ref.dtype)
    oe_ref[...] = e_new.astype(oe_ref.dtype)


def sdga_aggregate(updates: jax.Array, staleness: jax.Array,
                   params: jax.Array, mom: jax.Array, ema: jax.Array, *,
                   server_lr: float, alpha: float = 0.5,
                   momentum: float = 0.8, ema_anchor: float = 0.05,
                   ema_decay: float = 0.95, block_d: int = BLOCK_D,
                   interpret: bool = True, discount: str = "poly"):
    """Fused SDGA round.  updates (K, D), staleness (K,), params/mom/ema
    (D,) -> (new_params, new_mom, new_ema), all (D,).  ``discount="poly"``
    (default) reads ``staleness`` as tau and discounts in-kernel;
    ``"none"`` reads it as precomputed final weights (the adaptive
    scheduling policies' externally-reweighted path)."""
    assert discount in _DISCOUNTS
    K, D = updates.shape
    pad = (-D) % block_d
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
        params = jnp.pad(params, (0, pad))
        mom = jnp.pad(mom, (0, pad))
        ema = jnp.pad(ema, (0, pad))
    Dp = D + pad
    vec_spec = pl.BlockSpec((block_d,), lambda i: (i,))
    kern = functools.partial(
        _sdga_kernel, server_lr=server_lr, alpha=alpha, momentum=momentum,
        ema_anchor=ema_anchor, ema_decay=ema_decay, discount=discount)
    outs = pl.pallas_call(
        kern,
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((K,), lambda i: (0,)),
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
            vec_spec, vec_spec, vec_spec,
        ],
        out_specs=[vec_spec, vec_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((Dp,), params.dtype),
            jax.ShapeDtypeStruct((Dp,), jnp.float32),
            jax.ShapeDtypeStruct((Dp,), jnp.float32),
        ],
        interpret=interpret,
    )(staleness, updates, params, mom, ema)
    return tuple(o[:D] for o in outs)


# ---------------------------------------------------------------------------
# int8 flat channel: fused dequantize + aggregate (+ server step)
# ---------------------------------------------------------------------------


def _scale_spec(block_d: int, qblock: int, k: int) -> pl.BlockSpec:
    """Block of the transposed (Dq/qblock, K) scale array that grid step
    i reads: (block_d/qblock, K) — see the module docstring for why the
    scales travel transposed."""
    return pl.BlockSpec((block_d // qblock, k), lambda i: (i, 0))


def _col_scale_spec(block_d: int, qblock: int) -> pl.BlockSpec:
    """One upload row's scales as a (Dq/qblock, 1) column: a rank-1
    (block_d/qblock,) block is refused unless it spans 128 scales."""
    return pl.BlockSpec((block_d // qblock, 1), lambda i: (i, 0))


def check_tiling(block_d: int, qblock: int) -> None:
    """The compiled quantized kernels' tiling rule: a grid step reads
    block_d/qblock scales per row on the sublane axis, so it must be a
    whole multiple of 8 (interpret mode accepts any qblock multiple)."""
    assert block_d % qblock == 0 and (block_d // qblock) % 8 == 0, (
        f"block_d={block_d} must hold a multiple of 8 blocks of "
        f"qblock={qblock} lanes")


def _dequant_tile(q, s_t, qblock: int):
    """(K, BD) int8 tile + (BD/qblock, K) transposed scales -> (K, BD)
    f32 in VMEM."""
    K, BD = q.shape
    return (q.astype(jnp.float32).reshape(K, BD // qblock, qblock)
            * s_t.T[:, :, None]).reshape(K, BD)


def _agg_q8_kernel(w_ref, q_ref, s_ref, p_ref, o_ref, *, server_lr: float,
                   mode: str, alpha: float, discount: str, qblock: int):
    """One (K, BLOCK_D) int8 tile: blockwise dequantize in VMEM, then the
    same weighted reduction / server step (or fedasync mix) as the f32
    kernel."""
    w = _weights(w_ref[...], alpha, discount)  # (K,)
    u = _dequant_tile(q_ref[...], s_ref[...], qblock)  # (K, BLOCK_D) f32
    p = p_ref[...].astype(jnp.float32)
    if mode == "mix":
        g = _matvec(w, u)
        o_ref[...] = ((1.0 - jnp.sum(w)) * p + g).astype(o_ref.dtype)
        return
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    g = _matvec(w, u) / wsum
    o_ref[...] = (p - server_lr * g).astype(o_ref.dtype)


def _avg_q8_kernel(w_ref, q_ref, s_ref, o_ref, *, server_lr: float,
                   mode: str, alpha: float, discount: str, qblock: int):
    del server_lr
    w = _weights(w_ref[...], alpha, discount)
    u = _dequant_tile(q_ref[...], s_ref[...], qblock)
    g = _matvec(w, u)
    if mode != "sum":  # "avg" normalizes; "sum" is the per-shard partial
        g = g / jnp.maximum(jnp.sum(w), 1e-12)
    o_ref[...] = g.astype(o_ref.dtype)


def _pad_q8(q, scales, block_d: int, qblock: int):
    """Pad the quantized buffer from Dq to a block_d multiple and
    transpose the scales to the kernels' (Dp/qblock, K) layout.  Padding
    blocks get scale 0 so they dequantize to exact zeros."""
    K, Dq = q.shape
    assert block_d % qblock == 0, (block_d, qblock)
    assert Dq % qblock == 0, (Dq, qblock)
    assert scales.shape == (K, Dq // qblock), (scales.shape, q.shape)
    pad = (-Dq) % block_d
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad)))
        scales = jnp.pad(scales, ((0, 0), (0, pad // qblock)))
    return q, scales.T, Dq + pad


def safl_aggregate_q8(q: jax.Array, scales: jax.Array, weights: jax.Array,
                      params: jax.Array | None = None,
                      server_lr: float = 1.0, mode: str = "fedsgd",
                      qblock: int = QBLOCK, block_d: int = BLOCK_D,
                      interpret: bool = True, alpha: float = 0.5,
                      discount: str = "none") -> jax.Array:
    """Quantized-channel ``safl_aggregate``: q (K, Dq) int8, scales
    (K, Dq/qblock) f32, weights (K,), params (D,) [fedsgd / mix] -> (D,)
    (fedsgd / mix) or (Dq,) (avg / sum — ``"sum"`` is the unnormalized
    per-shard partial for the mesh-sharded reduction).  Dequantize,
    discount, reduction and server step run in one pass over the int8
    buffer (f32 updates never touch HBM)."""
    assert discount in _DISCOUNTS
    K, Dq = q.shape
    q, scales, Dp = _pad_q8(q, scales, block_d, qblock)
    grid = (Dp // block_d,)
    s_spec = _scale_spec(block_d, qblock, K)
    if mode in ("fedsgd", "mix"):
        assert params is not None
        D = params.shape[0]
        assert D <= Dq, (D, Dq)
        p = jnp.pad(params, (0, Dp - D)) if D < Dp else params
        args = (weights, q, scales, p)
        in_specs = [
            pl.BlockSpec((K,), lambda i: (0,)),
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
            s_spec,
            pl.BlockSpec((block_d,), lambda i: (i,)),
        ]
        kern, out_dtype, out_len = _agg_q8_kernel, params.dtype, D
    else:
        args = (weights, q, scales)
        in_specs = [
            pl.BlockSpec((K,), lambda i: (0,)),
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
            s_spec,
        ]
        kern, out_dtype, out_len = _avg_q8_kernel, jnp.float32, Dq
    out = pl.pallas_call(
        functools.partial(kern, server_lr=server_lr, mode=mode, alpha=alpha,
                          discount=discount, qblock=qblock),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_d,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Dp,), out_dtype),
        interpret=interpret,
    )(*args)
    return out[:out_len]


def _sdga_q8_kernel(tau_ref, q_ref, s_ref, p_ref, m_ref, e_ref,
                    op_ref, om_ref, oe_ref, *, server_lr: float,
                    alpha: float, momentum: float, ema_anchor: float,
                    ema_decay: float, qblock: int, discount: str):
    w = _weights(tau_ref[...], alpha, discount)
    u = _dequant_tile(q_ref[...], s_ref[...], qblock)
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    g = _matvec(w, u) / wsum
    m_new = momentum * m_ref[...].astype(jnp.float32) + g
    p = p_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    p_new = p - server_lr * m_new + ema_anchor * (e - p)
    e_new = ema_decay * e + (1.0 - ema_decay) * p_new
    op_ref[...] = p_new.astype(op_ref.dtype)
    om_ref[...] = m_new.astype(om_ref.dtype)
    oe_ref[...] = e_new.astype(oe_ref.dtype)


def sdga_aggregate_q8(q: jax.Array, scales: jax.Array, staleness: jax.Array,
                      params: jax.Array, mom: jax.Array, ema: jax.Array, *,
                      server_lr: float, alpha: float = 0.5,
                      momentum: float = 0.8, ema_anchor: float = 0.05,
                      ema_decay: float = 0.95, qblock: int = QBLOCK,
                      block_d: int = BLOCK_D, interpret: bool = True,
                      discount: str = "poly"):
    """Quantized-channel SDGA round: q (K, Dq) int8, scales (K, Dq/qblock),
    staleness (K,), params/mom/ema (D,) -> (new_params, new_mom, new_ema),
    all (D,), with blockwise dequantize fused into the single pass.
    ``discount`` as in :func:`sdga_aggregate`."""
    assert discount in _DISCOUNTS
    K, Dq = q.shape
    D = params.shape[0]
    assert D <= Dq, (D, Dq)
    q, scales, Dp = _pad_q8(q, scales, block_d, qblock)
    pad = Dp - D
    if pad:
        params = jnp.pad(params, (0, pad))
        mom = jnp.pad(mom, (0, pad))
        ema = jnp.pad(ema, (0, pad))
    vec_spec = pl.BlockSpec((block_d,), lambda i: (i,))
    kern = functools.partial(
        _sdga_q8_kernel, server_lr=server_lr, alpha=alpha, momentum=momentum,
        ema_anchor=ema_anchor, ema_decay=ema_decay, qblock=qblock,
        discount=discount)
    outs = pl.pallas_call(
        kern,
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((K,), lambda i: (0,)),
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
            _scale_spec(block_d, qblock, K),
            vec_spec, vec_spec, vec_spec,
        ],
        out_specs=[vec_spec, vec_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((Dp,), params.dtype),
            jax.ShapeDtypeStruct((Dp,), jnp.float32),
            jax.ShapeDtypeStruct((Dp,), jnp.float32),
        ],
        interpret=interpret,
    )(staleness, q, scales, params, mom, ema)
    return tuple(o[:D] for o in outs)


# ---------------------------------------------------------------------------
# packed int4 flat channel: fused unpack + dequantize + aggregate
# ---------------------------------------------------------------------------


def _q4_nibbles(qp):
    """(K, BD/2) packed int8 tile -> its (low, high) nibbles as i32.

    Byte j holds lane 2j in its low nibble and lane 2j+1 in its high one,
    sign-extended from the symmetric [-7, 7] grid — all in VMEM, so the
    HBM read of the K x D tile is half the q8 bytes."""
    u = qp.astype(jnp.int32)  # nibble math in i32: Mosaic has no i8 shift
    lo = u & 0xF
    hi = (u >> 4) & 0xF
    return (jnp.where(lo > 7, lo - 16, lo), jnp.where(hi > 7, hi - 16, hi))


def _q4_halves(qp, s_t, qblock: int):
    """(K, BD/2) packed int8 tile + (BD/qblock, K) transposed scales ->
    the dequantized (low, high) nibble lanes, two (K, BD/2) f32 tiles.
    A qblock of lanes is qblock/2 bytes, so both halves dequantize with
    the same scales.  Callers reduce over K first and interleave only the
    result: Mosaic's compile time for a lane interleave grows with its
    rows (minutes at K = 64, block_d = 16384)."""
    return tuple(_dequant_tile(h, s_t, qblock // 2) for h in _q4_nibbles(qp))


def _interleave(lo, hi):
    """(..., n) even and odd lanes -> (..., 2n)."""
    return jnp.stack([lo, hi], axis=-1).reshape(*lo.shape[:-1],
                                                2 * lo.shape[-1])


def _matvec_q4(w, qp, s_t, qblock: int):
    """(K,) weights x packed (K, BD/2) q4 tile -> (1, BD) weighted row
    sum, each nibble half reduced over K before the one interleave of the
    result.  Mosaic lowers a lane interleave only on 2-D rows, and cannot
    reshape the (1, BD) row to (BD,): the q4 kernels' vectors travel as
    (1, D) rows."""
    red = lambda u: jnp.sum(w[:, None] * u, axis=0, keepdims=True)
    return _interleave(*(red(h) for h in _q4_halves(qp, s_t, qblock)))


def _q4_params(block_d: int, interpret: bool) -> dict:
    """Scoped VMEM for the q4 kernels.  The lane interleave's (..., 2)
    intermediate pads to 128 lanes, about 2 KiB of VMEM per tile lane:
    past the default 16 MiB once FlatServer widens the tile for a coarse
    qblock (31 MiB at block_d = 16384).  Twice that, never below the
    default."""
    if interpret:
        return {}
    return dict(compiler_params=pltpu.CompilerParams(
        vmem_limit_bytes=max(16 << 20, 4096 * block_d)))


def _pad_q4(qp, scales, block_d: int, qblock: int):
    """Pad the packed buffer from Dq/2 to a block_d/2 multiple and
    transpose the scales as :func:`_pad_q8` does.  Padding blocks get
    scale 0 so they dequantize to exact zeros."""
    K, half = qp.shape
    Dq = 2 * half
    assert block_d % qblock == 0 and block_d % 2 == 0, (block_d, qblock)
    assert Dq % qblock == 0, (Dq, qblock)
    assert scales.shape == (K, Dq // qblock), (scales.shape, qp.shape)
    pad = (-Dq) % block_d
    if pad:
        qp = jnp.pad(qp, ((0, 0), (0, pad // 2)))
        scales = jnp.pad(scales, ((0, 0), (0, pad // qblock)))
    return qp, scales.T, Dq + pad


def _agg_q4_kernel(w_ref, qp_ref, s_ref, p_ref, o_ref, *, server_lr: float,
                   mode: str, alpha: float, discount: str, qblock: int):
    """One (K, BLOCK_D) logical tile read as (K, BLOCK_D/2) packed bytes:
    unpack + blockwise dequantize in VMEM, then the same weighted
    reduction / server step (or fedasync mix) as the f32 kernel."""
    w = _weights(w_ref[...], alpha, discount)  # (K,)
    g = _matvec_q4(w, qp_ref[...], s_ref[...], qblock)  # (1, BLOCK_D)
    p = p_ref[...].astype(jnp.float32)
    if mode == "mix":
        o_ref[...] = ((1.0 - jnp.sum(w)) * p + g).astype(o_ref.dtype)
        return
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    g = g / wsum
    o_ref[...] = (p - server_lr * g).astype(o_ref.dtype)


def _avg_q4_kernel(w_ref, qp_ref, s_ref, o_ref, *, server_lr: float,
                   mode: str, alpha: float, discount: str, qblock: int):
    del server_lr
    w = _weights(w_ref[...], alpha, discount)
    g = _matvec_q4(w, qp_ref[...], s_ref[...], qblock)
    if mode != "sum":  # "avg" normalizes; "sum" is the per-shard partial
        g = g / jnp.maximum(jnp.sum(w), 1e-12)
    o_ref[...] = g.astype(o_ref.dtype)


def safl_aggregate_q4(qp: jax.Array, scales: jax.Array, weights: jax.Array,
                      params: jax.Array | None = None,
                      server_lr: float = 1.0, mode: str = "fedsgd",
                      qblock: int = QBLOCK, block_d: int = BLOCK_D,
                      interpret: bool = True, alpha: float = 0.5,
                      discount: str = "none") -> jax.Array:
    """Packed-int4 ``safl_aggregate``: qp (K, Dq/2) int8 (two nibbles per
    byte), scales (K, Dq/qblock) f32, weights (K,), params (D,) [fedsgd /
    mix] -> (D,) (fedsgd / mix) or (Dq,) (avg / sum).  Nibble unpack,
    blockwise dequantize, discount, reduction and server step run in one
    pass over the packed buffer — the K x D HBM read is 8x fewer bytes
    than the f32 channel.  Oracle: :func:`repro.kernels.ref.safl_agg_q4_ref`
    and friends."""
    assert discount in _DISCOUNTS
    K, half = qp.shape
    Dq = 2 * half
    qp, scales, Dp = _pad_q4(qp, scales, block_d, qblock)
    grid = (Dp // block_d,)
    s_spec = _scale_spec(block_d, qblock, K)
    row_spec = pl.BlockSpec((1, block_d), lambda i: (0, i))
    if mode in ("fedsgd", "mix"):
        assert params is not None
        D = params.shape[0]
        assert D <= Dq, (D, Dq)
        p = jnp.pad(params, (0, Dp - D)) if D < Dp else params
        args = (weights, qp, scales, p.reshape(1, Dp))
        in_specs = [
            pl.BlockSpec((K,), lambda i: (0,)),
            pl.BlockSpec((K, block_d // 2), lambda i: (0, i)),
            s_spec,
            row_spec,
        ]
        kern, out_dtype, out_len = _agg_q4_kernel, params.dtype, D
    else:
        args = (weights, qp, scales)
        in_specs = [
            pl.BlockSpec((K,), lambda i: (0,)),
            pl.BlockSpec((K, block_d // 2), lambda i: (0, i)),
            s_spec,
        ]
        kern, out_dtype, out_len = _avg_q4_kernel, jnp.float32, Dq
    out = pl.pallas_call(
        functools.partial(kern, server_lr=server_lr, mode=mode, alpha=alpha,
                          discount=discount, qblock=qblock),
        grid=grid,
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, Dp), out_dtype),
        interpret=interpret,
        **_q4_params(block_d, interpret),
    )(*args)
    return out[0, :out_len]


def _fold_q4_kernel(s_ref, a_ref, qp_ref, sc_ref, o_ref, *, qblock: int):
    """Streaming fold of one packed-q4 row tile: unpack + blockwise
    dequantize the (1, BLOCK_D/2) byte slice in VMEM, then
    o = beta*a + w*u (2-D rows, as in :func:`_fold_q8_kernel`)."""
    u = _dequant_tile(_interleave(*_q4_nibbles(qp_ref[...])), sc_ref[...],
                      qblock)
    o_ref[...] = s_ref[0] * a_ref[...].astype(jnp.float32) + s_ref[1] * u


def safl_fold_q4(acc: jax.Array, qp_row: jax.Array, scales_row: jax.Array,
                 w, beta=1.0, qblock: int = QBLOCK,
                 block_d: int = BLOCK_D, interpret: bool = True) -> jax.Array:
    """Packed-q4 streaming fold: acc (Dq,) f32, qp_row (Dq/2,) int8,
    scales_row (Dq/qblock,) f32 -> beta*acc + w*dequant(unpack(qp_row)),
    one fused pass (oracle :func:`repro.kernels.ref.fold_q4_ref`)."""
    Dq = acc.shape[0]
    assert qp_row.shape == (Dq // 2,) and block_d % qblock == 0
    pad = (-Dq) % block_d
    if pad:
        acc = jnp.pad(acc, (0, pad))
        qp_row = jnp.pad(qp_row, (0, pad // 2))
        scales_row = jnp.pad(scales_row, (0, pad // qblock))
    Dp = Dq + pad
    sw = jnp.stack([jnp.asarray(beta, jnp.float32),
                    jnp.asarray(w, jnp.float32)])
    row_spec = pl.BlockSpec((1, block_d), lambda i: (0, i))
    out = pl.pallas_call(
        functools.partial(_fold_q4_kernel, qblock=qblock),
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,)),
            row_spec,
            pl.BlockSpec((1, block_d // 2), lambda i: (0, i)),
            _col_scale_spec(block_d, qblock),
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        interpret=interpret,
        **_q4_params(block_d, interpret),
    )(sw, acc[None], qp_row[None], scales_row[:, None])
    return out[0, :Dq]


def _sdga_q4_kernel(tau_ref, qp_ref, s_ref, p_ref, m_ref, e_ref,
                    op_ref, om_ref, oe_ref, *, server_lr: float,
                    alpha: float, momentum: float, ema_anchor: float,
                    ema_decay: float, qblock: int, discount: str):
    w = _weights(tau_ref[...], alpha, discount)
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    g = _matvec_q4(w, qp_ref[...], s_ref[...], qblock) / wsum
    m_new = momentum * m_ref[...].astype(jnp.float32) + g
    p = p_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    p_new = p - server_lr * m_new + ema_anchor * (e - p)
    e_new = ema_decay * e + (1.0 - ema_decay) * p_new
    op_ref[...] = p_new.astype(op_ref.dtype)
    om_ref[...] = m_new.astype(om_ref.dtype)
    oe_ref[...] = e_new.astype(oe_ref.dtype)


def sdga_aggregate_q4(qp: jax.Array, scales: jax.Array, staleness: jax.Array,
                      params: jax.Array, mom: jax.Array, ema: jax.Array, *,
                      server_lr: float, alpha: float = 0.5,
                      momentum: float = 0.8, ema_anchor: float = 0.05,
                      ema_decay: float = 0.95, qblock: int = QBLOCK,
                      block_d: int = BLOCK_D, interpret: bool = True,
                      discount: str = "poly"):
    """Packed-q4 SDGA round: qp (K, Dq/2) int8, scales (K, Dq/qblock),
    staleness (K,), params/mom/ema (D,) -> (new_params, new_mom, new_ema),
    all (D,), with nibble unpack + blockwise dequantize fused into the
    single pass.  ``discount`` as in :func:`sdga_aggregate`."""
    assert discount in _DISCOUNTS
    K, half = qp.shape
    Dq = 2 * half
    D = params.shape[0]
    assert D <= Dq, (D, Dq)
    qp, scales, Dp = _pad_q4(qp, scales, block_d, qblock)
    pad = Dp - D
    # (1, Dp) rows, as the q4 matvec yields (see :func:`_matvec_q4`)
    params, mom, ema = (jnp.pad(v, (0, pad)).reshape(1, Dp)
                        for v in (params, mom, ema))
    vec_spec = pl.BlockSpec((1, block_d), lambda i: (0, i))
    kern = functools.partial(
        _sdga_q4_kernel, server_lr=server_lr, alpha=alpha, momentum=momentum,
        ema_anchor=ema_anchor, ema_decay=ema_decay, qblock=qblock,
        discount=discount)
    outs = pl.pallas_call(
        kern,
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((K,), lambda i: (0,)),
            pl.BlockSpec((K, block_d // 2), lambda i: (0, i)),
            _scale_spec(block_d, qblock, K),
            vec_spec, vec_spec, vec_spec,
        ],
        out_specs=[vec_spec, vec_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, Dp), params.dtype),
            jax.ShapeDtypeStruct((1, Dp), jnp.float32),
            jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        ],
        interpret=interpret,
        **_q4_params(block_d, interpret),
    )(staleness, qp, scales, params, mom, ema)
    return tuple(o[0, :D] for o in outs)


# ---------------------------------------------------------------------------
# defense screening: fused per-row isfinite + L2 pass (PR 8)
# ---------------------------------------------------------------------------


def _screen_kernel(u_ref, o_ref):
    """One (K, BLOCK_D) tile of the screening reduction: the (K,) output
    block is revisited every grid step and accumulates the per-row sum
    of squares — NaN/Inf payload lanes poison the sum, so the caller's
    ``isfinite(sumsq)`` is the integrity verdict and ``sqrt`` the norm."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    u = u_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.sum(u * u, axis=1)


def screen_rows(rows: jax.Array, block_d: int = BLOCK_D,
                interpret: bool = True) -> jax.Array:
    """f32-wire screening pass: rows (K, D) -> (K,) f32 sum of squares,
    one streaming pass (oracle :func:`repro.kernels.ref.screen_sumsq_ref`).
    Zero padding to the block size contributes exact zeros."""
    K, D = rows.shape
    pad = (-D) % block_d
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    Dp = D + pad
    return pl.pallas_call(
        _screen_kernel,
        grid=(Dp // block_d,),
        in_specs=[pl.BlockSpec((K, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((K,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((K,), jnp.float32),
        interpret=interpret,
    )(rows)


def _screen_q8_kernel(q_ref, s_ref, o_ref, *, qblock: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    u = _dequant_tile(q_ref[...], s_ref[...], qblock)
    o_ref[...] += jnp.sum(u * u, axis=1)


def screen_rows_q8(q: jax.Array, scales: jax.Array, qblock: int = QBLOCK,
                   block_d: int = BLOCK_D, interpret: bool = True
                   ) -> jax.Array:
    """q8/topk screening pass: q (K, Nq) int8 + scales (K, Nq/qblock) ->
    (K,) sum of squares of the dequantized rows, dequant fused into the
    reduction tiles (oracle :func:`repro.kernels.ref.screen_sumsq_q8_ref`;
    the topk wire screens its compacted value lanes through this same
    grid — padding coordinates carry scale 0 and contribute nothing)."""
    K = q.shape[0]
    q, scales, Dp = _pad_q8(q, scales, block_d, qblock)
    return pl.pallas_call(
        functools.partial(_screen_q8_kernel, qblock=qblock),
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
            _scale_spec(block_d, qblock, K),
        ],
        out_specs=pl.BlockSpec((K,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((K,), jnp.float32),
        interpret=interpret,
    )(q, scales)


def _screen_q4_kernel(qp_ref, s_ref, o_ref, *, qblock: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    lo, hi = _q4_halves(qp_ref[...], s_ref[...], qblock)
    o_ref[...] += jnp.sum(lo * lo, axis=1) + jnp.sum(hi * hi, axis=1)


def screen_rows_q4(qp: jax.Array, scales: jax.Array, qblock: int = QBLOCK,
                   block_d: int = BLOCK_D, interpret: bool = True
                   ) -> jax.Array:
    """Packed-q4 screening pass: qp (K, Dq/2) int8 + scales -> (K,) sum
    of squares with the nibble unpack + dequantize fused into the tiles
    (oracle :func:`repro.kernels.ref.screen_sumsq_q4_ref`)."""
    K = qp.shape[0]
    qp, scales, Dp = _pad_q4(qp, scales, block_d, qblock)
    return pl.pallas_call(
        functools.partial(_screen_q4_kernel, qblock=qblock),
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((K, block_d // 2), lambda i: (0, i)),
            _scale_spec(block_d, qblock, K),
        ],
        out_specs=pl.BlockSpec((K,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((K,), jnp.float32),
        interpret=interpret,
    )(qp, scales)
