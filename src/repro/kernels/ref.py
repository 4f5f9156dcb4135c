"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np


def safl_agg_ref(updates: jax.Array, weights: jax.Array,
                 params: jax.Array, server_lr: float) -> jax.Array:
    """Fused FedSGD server step over a K-stacked flat update buffer.

    updates (K, D) f32, weights (K,), params (D,) ->
        params - lr * sum_k w_k u_k / sum_k w_k        (Eq. 4-5)
    """
    w = weights.astype(jnp.float32)
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    g = jnp.einsum("k,kd->d", w, updates.astype(jnp.float32)) / wsum
    return (params.astype(jnp.float32) - server_lr * g).astype(params.dtype)


def weighted_avg_ref(updates: jax.Array, weights: jax.Array) -> jax.Array:
    """FedAvg target: weighted mean over K (Eq. 6). updates (K, D)."""
    w = weights.astype(jnp.float32)
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    return jnp.einsum("k,kd->d", w, updates.astype(jnp.float32)) / wsum


def weighted_sum_ref(updates: jax.Array, weights: jax.Array) -> jax.Array:
    """Unnormalized weighted row sum w @ u -> (D,) f32 — the per-shard
    partial of the mesh-sharded server reduction (oracle for the kernels'
    ``mode="sum"``; the psum over shards happens in
    repro.sharding.flat.podwise_sums)."""
    return jnp.einsum("k,kd->d", weights.astype(jnp.float32),
                      updates.astype(jnp.float32))


def fold_ref(acc: jax.Array, vec: jax.Array, w, beta=1.0) -> jax.Array:
    """One streaming accumulate-on-arrival fold: acc <- beta*acc + w*vec.

    acc (D,) f32 running partial sum, vec (D,) one arriving upload, w the
    upload's FINAL aggregation weight (discount-at-ingest: the engine
    folds the (1+tau)^-alpha discount / data size / policy score into w
    before dispatch), beta the decay on the existing accumulator (1.0
    for the sum modes; 1 - a_i for the fedasync sequential mix, where it
    realizes prod_{j>i}(1 - a_j) one arrival at a time).  Oracle for
    kernels.safl_agg.safl_fold; a chain of these folds is bitwise equal
    to ``weighted_sum_ref`` on the same rows (XLA CPU reduces einsum
    rows in order) — the streaming-vs-buffered parity contract.
    """
    return (jnp.asarray(beta, jnp.float32) * acc.astype(jnp.float32)
            + jnp.asarray(w, jnp.float32) * vec.astype(jnp.float32))


def mix_ref(acc: jax.Array, vec: jax.Array, a) -> jax.Array:
    """One fedasync mix step acc <- (1 - a) acc + a vec, written as
    acc + a (vec - acc).  With one product there is one way to contract
    it into an FMA, so the streaming fold program and the buffered
    :func:`fedasync_rates_flat_ref` recursion round identically.  The
    two-product form ``beta*acc + a*vec`` lets XLA's CPU emitter fuse
    either product, and it picks differently in the two programs (a
    one-ulp drift)."""
    acc = acc.astype(jnp.float32)
    return acc + jnp.asarray(a, jnp.float32) * (vec.astype(jnp.float32)
                                                 - acc)


def fold_q8_ref(acc: jax.Array, q_row: jax.Array, s_row: jax.Array,
                w, qblock: int, beta=1.0) -> jax.Array:
    """Streaming fold of one quantized upload row: blockwise dequantize
    q_row (Dq,) int8 with s_row (Dq//qblock,) f32 scales, then
    :func:`fold_ref` — the q8 accumulate-on-arrival oracle."""
    Dq = q_row.shape[0]
    u = (q_row.astype(jnp.float32).reshape(Dq // qblock, qblock)
         * s_row[:, None]).reshape(Dq)
    return fold_ref(acc, u, w, beta)


def fedasync_rates_flat_ref(updates: jax.Array, rates: jax.Array,
                            params: jax.Array):
    """Sequential fedasync mix over a flat (K, D) buffer in (S, P) form.

    K per-update mixes p <- (1 - a_i) p + a_i u_i decompose into a
    foldable pair: S accumulates a_i u_i prod_{j>i}(1 - a_j) one row at
    a time (the :func:`fold_ref` recursion with beta = 1 - a_i, w = a_i,
    computed as :func:`mix_ref`) and P = prod_i (1 - a_i), with the
    final model P p + S.  This is the buffered oracle the streaming
    channel is bit-exact against: both run the identical
    :func:`mix_ref` recursion, unlike the
    coefficient-einsum form (``fedasync_flat_ref``), whose reduction
    order differs.  Returns (mixed, weight_sum = 1 - P).
    """
    a = rates.astype(jnp.float32)
    u = updates.astype(jnp.float32)

    def body(i, sp):
        s, prod = sp
        return mix_ref(s, u[i], a[i]), prod * (1.0 - a[i])

    s, prod = jax.lax.fori_loop(
        0, a.shape[0], body,
        (jnp.zeros(params.shape[0], jnp.float32), jnp.float32(1.0)))
    mixed = prod * params.astype(jnp.float32) + s
    return mixed.astype(params.dtype), 1.0 - prod


def fedasync_rates_flat_q8_ref(q: jax.Array, scales: jax.Array,
                               rates: jax.Array, params: jax.Array,
                               qblock: int):
    """Sequential (S, P) fedasync mix with per-row dequantize in the fold
    — the q8 buffered oracle for the streaming rates channel."""
    a = rates.astype(jnp.float32)
    d = params.shape[0]

    def body(i, sp):
        s, prod = sp
        u = fold_q8_ref(jnp.zeros((q.shape[1],), jnp.float32),
                        q[i], scales[i], 1.0, qblock)[:d]
        return (1.0 - a[i]) * s + a[i] * u, prod * (1.0 - a[i])

    s, prod = jax.lax.fori_loop(
        0, a.shape[0], body,
        (jnp.zeros(d, jnp.float32), jnp.float32(1.0)))
    mixed = prod * params.astype(jnp.float32) + s
    return mixed.astype(params.dtype), 1.0 - prod


def fedbuff_flat_ref(updates: jax.Array, staleness: jax.Array,
                     params: jax.Array, server_lr: float,
                     alpha: float = 0.5) -> jax.Array:
    """Staleness-discounted buffered gradient step over a flat buffer:
    weights (1+tau)^(-alpha), then the Eq. 4-5 server step."""
    w = jnp.power(1.0 + staleness.astype(jnp.float32), -alpha)
    return safl_agg_ref(updates, w, params, server_lr)


def fedasync_flat_ref(updates: jax.Array, coeffs: jax.Array,
                      params: jax.Array) -> jax.Array:
    """Folded fedasync mix over a flat (K, D) buffer.

    K sequential per-update mixes p <- (1 - a_i) p + a_i u_i are one
    linear combination (1 - sum(c)) p + c @ u when c_i = a_i *
    prod_{j>i} (1 - a_j) (repro.core.aggregation.fedasync_coefficients);
    the coefficients already carry the staleness discount, so no
    normalization and no in-kernel discount.
    """
    c = coeffs.astype(jnp.float32)
    mixed = ((1.0 - jnp.sum(c)) * params.astype(jnp.float32)
             + jnp.einsum("k,kd->d", c, updates.astype(jnp.float32)))
    return mixed.astype(params.dtype)


def fedasync_flat_q8_ref(q: jax.Array, scales: jax.Array,
                         coeffs: jax.Array, params: jax.Array,
                         qblock: int) -> jax.Array:
    """Fused dequantize + folded fedasync mix oracle (int8 flat channel)."""
    u = dequant_flat_ref(q, scales, qblock)[:, :params.shape[0]]
    return fedasync_flat_ref(u, coeffs, params)


def sdga_step_from_mean(g: jax.Array, params: jax.Array, mom: jax.Array,
                        ema: jax.Array, *, server_lr: float,
                        momentum: float, ema_anchor: float,
                        ema_decay: float):
    """The SDGA server step given the aggregated gradient mean g (D,) —
    the single definition of the momentum / EMA-anchor update shared by
    the flat oracle and the quantized CPU path."""
    m_new = momentum * mom.astype(jnp.float32) + g
    p = params.astype(jnp.float32)
    e = ema.astype(jnp.float32)
    p_new = p - server_lr * m_new + ema_anchor * (e - p)
    e_new = ema_decay * e + (1.0 - ema_decay) * p_new
    return p_new.astype(params.dtype), m_new, e_new


def sdga_flat_ref(updates: jax.Array, staleness: jax.Array,
                  params: jax.Array, mom: jax.Array, ema: jax.Array, *,
                  server_lr: float, alpha: float = 0.5,
                  momentum: float = 0.8, ema_anchor: float = 0.05,
                  ema_decay: float = 0.95):
    """Full SDGA round over a flat (K, D) buffer — oracle for
    kernels.safl_agg.sdga_aggregate."""
    w = jnp.power(1.0 + staleness.astype(jnp.float32), -alpha)
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    g = jnp.einsum("k,kd->d", w, updates.astype(jnp.float32)) / wsum
    return sdga_step_from_mean(g, params, mom, ema, server_lr=server_lr,
                               momentum=momentum, ema_anchor=ema_anchor,
                               ema_decay=ema_decay)


def dequant_flat_ref(q: jax.Array, scales: jax.Array,
                     qblock: int) -> jax.Array:
    """Blockwise-dequantize a quantized flat update buffer.

    q (K, Dq) int8 with Dq a multiple of qblock, scales (K, Dq//qblock)
    f32 -> (K, Dq) f32.  Padding blocks carry scale 0 and dequantize to 0.
    """
    K, Dq = q.shape
    return (q.astype(jnp.float32).reshape(K, Dq // qblock, qblock)
            * scales[:, :, None]).reshape(K, Dq)


INT8_DOT_MIN_K = 32  # rows at which the int8-dot path beats the fusion


def int8dot_auto(k: int) -> bool:
    """Whether the int8-dot reduction should engage automatically for K rows.

    The integer-GEMM form only pays where the backend has native int8
    dot units (TPU / recent GPUs).  XLA **CPU emulates** the int8
    einsum: at K=64, D=1M, qblock=512 the int8-dot path measures
    ~272 ms/agg vs ~33 ms for the chunked float form (and ~35 ms for
    the threaded f32 einsum) — the `speedup_q8_vs_flat: 0.15` K=64
    regression in BENCH_agg.json.  Auto dispatch therefore requires
    both ``k >= INT8_DOT_MIN_K`` *and* a non-CPU default backend.

    ``REPRO_INT8_DOT=1`` / ``=0`` overrides the platform gate (but not
    the K threshold) so tests can pin the dispatch boundary on CPU.
    """
    env = os.environ.get("REPRO_INT8_DOT", "").strip()
    if env in ("0", "1"):
        return env == "1" and k >= INT8_DOT_MIN_K
    return k >= INT8_DOT_MIN_K and jax.default_backend() != "cpu"


def int8dot_coeff_scale(scales: jax.Array, weights: jax.Array) -> jax.Array:
    """(nb,) per-block absmax scale of the reduction coefficients
    c_kb = w_k * s_kb — the quantization granule of the int8-dot path.
    Split out so the mesh-sharded reduction can pmax it across shards
    (each shard must quantize against the GLOBAL coefficient absmax, or
    the sharded round diverges from the single-device one)."""
    c = weights.astype(jnp.float32)[:, None] * scales  # (K, nb)
    return jnp.max(jnp.abs(c), axis=0) / 127.0


def weighted_sum_q8_int8dot_ref(q: jax.Array, scales: jax.Array,
                                weights: jax.Array, qblock: int,
                                coeff_scale: jax.Array | None = None
                                ) -> jax.Array:
    """sum_k w_k * dequant(q_k) -> (Dq,) f32 via an int8 x int8 -> int32
    integer dot — the large-K CPU path of the quantized channel.

    The fused elementwise streaming form (:func:`weighted_sum_q8_ref`)
    is single-fusion-bound on XLA CPU: at K=64 it only reaches ~parity
    with the threaded f32 einsum.  This path keeps the reduction an
    integer *matmul* instead: the per-row reduction coefficient of block
    b is c_kb = w_k * s_kb, quantized per block over K with one f32
    absmax scale S_b (the same granule idea as the wire format, now
    applied to coefficients), so

        sum_k c_kb q_kb  ≈  S_b * sum_k cq_kb q_kb

    with the inner sum an int8 dot accumulated in int32 (|cq*q| <= 127^2,
    so K up to ~130k rows fits int32) that XLA lowers to a batched
    integer GEMM.  Coefficient rounding adds at most 0.5/127 of the
    block's largest |c| per row — the same order as the wire
    quantization noise itself.

    ``coeff_scale`` overrides the per-block coefficient absmax scale
    (:func:`int8dot_coeff_scale`): the mesh-sharded server passes the
    pod-wide pmax so every shard quantizes its coefficients on the same
    grid as the single-device round.
    """
    K, Dq = q.shape
    nb = Dq // qblock
    c = weights.astype(jnp.float32)[:, None] * scales  # (K, nb)
    if coeff_scale is None:
        coeff_scale = int8dot_coeff_scale(scales, weights)
    cs = jnp.maximum(coeff_scale, 1e-30)  # (nb,)
    cq = jnp.clip(jnp.round(c / cs[None, :]), -127, 127).astype(jnp.int8)
    acc = jnp.einsum("kb,kbq->bq", cq, q.reshape(K, nb, qblock),
                     preferred_element_type=jnp.int32)  # (nb, qblock) i32
    return (acc.astype(jnp.float32) * cs[:, None]).reshape(Dq)


def weighted_sum_q8_ref(q: jax.Array, scales: jax.Array,
                        weights: jax.Array, qblock: int,
                        chunk: int | None = None,
                        int8_dot: bool | None = None) -> jax.Array:
    """sum_k w_k * dequant(q_k) -> (Dq,) f32, streaming.

    Unlike ``dequant_flat_ref`` + einsum, this never materializes the f32
    (K, Dq) buffer: each chunk of rows is one fused elementwise XLA loop
    that reads int8 and folds the per-block scale into the reduction
    coefficient — the CPU fast path of the quantized channel (the ``*_q8``
    Pallas kernels are the TPU fast path).  K is a static shape, so the
    Python loops unroll at trace time.  ``chunk`` bounds how many int8
    rows one fused loop touches: a very wide fusion (measured at K=64)
    spills registers and runs slower than the f32 einsum, so past 16 rows
    the sum splits into 16-row partials with ``optimization_barrier``
    keeping XLA from re-fusing them back together (the partials cost one
    extra (D,) f32 round-trip each — the small-K single fusion is the
    fast case).

    ``int8_dot`` (default: auto via :func:`int8dot_auto` — K >=
    INT8_DOT_MIN_K *on a non-CPU backend*, overridable with
    ``REPRO_INT8_DOT``) dispatches to
    :func:`weighted_sum_q8_int8dot_ref` instead — per-block-quantized
    coefficients + int32-accumulated integer dot, the large-K regime
    where the single fused loop stops scaling on hardware with native
    int8 GEMM.  On XLA CPU the integer dot is emulated and ~8x slower
    than this chunked form at K=64, so auto never picks it there.
    """
    K, Dq = q.shape
    if int8_dot is None:
        int8_dot = int8dot_auto(K)
    if int8_dot:
        return weighted_sum_q8_int8dot_ref(q, scales, weights, qblock)
    if chunk is None:
        chunk = K if K <= 16 else 16
    w = weights.astype(jnp.float32)
    nb = Dq // qblock

    def span_sum(b0: int, b1: int) -> jax.Array:
        """Reduce blocks [b0, b1) over K -> ((b1-b0)*qblock,) f32."""
        out = None
        for k0 in range(0, K, chunk):
            acc = jnp.zeros((b1 - b0, qblock), jnp.float32)
            for k in range(k0, min(k0 + chunk, K)):
                coef = (w[k] * scales[k, b0:b1])[:, None]
                acc = acc + (q[k, b0 * qblock:b1 * qblock]
                             .astype(jnp.float32).reshape(-1, qblock)
                             * coef)
            if K > chunk:
                acc = jax.lax.optimization_barrier(acc)
            out = acc if out is None else out + acc
        return out.reshape((b1 - b0) * qblock)

    # two independent half-D root thunks let the XLA CPU runtime overlap
    # them across the intra-op pool (one monolithic fusion runs on a
    # single thread); the big-K chunked form gains nothing from it
    if K <= chunk and nb >= 2:
        return jnp.concatenate([span_sum(0, nb // 2),
                                span_sum(nb // 2, nb)])
    return span_sum(0, nb)


def safl_agg_q8_ref(q: jax.Array, scales: jax.Array, weights: jax.Array,
                    params: jax.Array, server_lr: float,
                    qblock: int) -> jax.Array:
    """Fused dequantize + FedSGD server step oracle (int8 flat channel)."""
    u = dequant_flat_ref(q, scales, qblock)[:, :params.shape[0]]
    return safl_agg_ref(u, weights, params, server_lr)


def weighted_avg_q8_ref(q: jax.Array, scales: jax.Array,
                        weights: jax.Array, qblock: int) -> jax.Array:
    """Fused dequantize + FedAvg weighted mean oracle (int8 flat channel)."""
    return weighted_avg_ref(dequant_flat_ref(q, scales, qblock), weights)


def sdga_flat_q8_ref(q: jax.Array, scales: jax.Array, staleness: jax.Array,
                     params: jax.Array, mom: jax.Array, ema: jax.Array, *,
                     qblock: int, server_lr: float, alpha: float = 0.5,
                     momentum: float = 0.8, ema_anchor: float = 0.05,
                     ema_decay: float = 0.95):
    """Fused dequantize + full SDGA round oracle (int8 flat channel)."""
    u = dequant_flat_ref(q, scales, qblock)[:, :params.shape[0]]
    return sdga_flat_ref(u, staleness, params, mom, ema,
                         server_lr=server_lr, alpha=alpha, momentum=momentum,
                         ema_anchor=ema_anchor, ema_decay=ema_decay)


def quantize_ref(x: jax.Array):
    """Blockwise int8 absmax quantization. x (R, B) -> (q s8, scales f32)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[:, 0]


def dequantize_ref(q: jax.Array, scales: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scales[:, None]


# ------------------------- packed int4 wire (q4) -------------------------

Q4_LEVELS = 7  # symmetric int4 grid [-7, 7]; -8 stays unused


def quantize_q4_ref(x: jax.Array, u: jax.Array):
    """Blockwise int4 absmax quantization with stochastic rounding.

    x (R, B) f32 and u (R, B) uniform [0, 1) draws -> (q int8 in
    [-7, 7], scales (R,) f32) with scale = absmax/7 (floored at 1e-12).
    q = floor(y) + Bernoulli(y - floor(y)) for y = clip(x/scale, ±7),
    so E[q * scale] = x inside the clip range: the rounding error is
    zero-mean and the client-side error-feedback residual telescopes
    across rounds instead of accumulating round-to-nearest bias.  The
    draws u must come from a counter-keyed PRNG (see
    core.flatbuf.PytreeCodec.ravel_delta_q4) so every engine path
    reproduces them bit-identically.
    """
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / Q4_LEVELS
    scale = jnp.maximum(scale, 1e-12)
    y = jnp.clip(x.astype(jnp.float32) / scale, -Q4_LEVELS, Q4_LEVELS)
    f = jnp.floor(y)
    q = f + (u < (y - f)).astype(jnp.float32)
    q = jnp.clip(q, -Q4_LEVELS, Q4_LEVELS)
    return q.astype(jnp.int8), scale[:, 0]


def pack_q4_ref(q: jax.Array) -> jax.Array:
    """(..., D) int8 nibbles in [-7, 7] -> (..., D//2) int8, two per byte.

    Lane 2j lands in the low nibble of byte j, lane 2j+1 in the high
    nibble (two's-complement uint8 arithmetic; the wire dtype stays
    int8 so the packed buffer reuses the q8 storage path).
    """
    u = q.astype(jnp.uint8) & 0xF
    lo, hi = u[..., 0::2], u[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_q4_ref(p: jax.Array) -> jax.Array:
    """(..., D//2) packed int8 -> (..., D) int8 nibbles, sign-extended."""
    u = p.astype(jnp.uint8)
    lo = (u & 0xF).astype(jnp.int32)
    hi = (u >> 4).astype(jnp.int32)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1).reshape(*p.shape[:-1],
                                               2 * p.shape[-1])
    return out.astype(jnp.int8)


def dequant_q4_flat_ref(p: jax.Array, scales: jax.Array,
                        qblock: int) -> jax.Array:
    """Unpack + blockwise-dequantize a packed q4 flat buffer.

    p (K, Dq//2) int8, scales (K, Dq//qblock) f32 -> (K, Dq) f32.
    Padding blocks carry scale 0 and dequantize to exact zeros.
    """
    q = unpack_q4_ref(p)
    K, Dq = q.shape
    return (q.astype(jnp.float32).reshape(K, Dq // qblock, qblock)
            * scales[:, :, None]).reshape(K, Dq)


def weighted_sum_q4_ref(p: jax.Array, scales: jax.Array,
                        weights: jax.Array, qblock: int,
                        chunk: int = 16) -> jax.Array:
    """sum_k w_k * dequant(unpack(p_k)) -> (Dq,) f32, streaming.

    Chunks of ``chunk`` rows are unpacked + dequantized and reduced per
    chunk, so at most a (chunk, Dq) f32 temporary exists at once — the
    CPU path of the q4 channel (the ``*_q4`` Pallas kernels fuse the
    nibble unpack into the aggregation tiles on TPU).
    """
    K = p.shape[0]
    Dq = 2 * p.shape[1]
    w = weights.astype(jnp.float32)
    out = jnp.zeros((Dq,), jnp.float32)
    for k0 in range(0, K, chunk):
        rows = dequant_q4_flat_ref(p[k0:k0 + chunk],
                                   scales[k0:k0 + chunk], qblock)
        out = out + jnp.einsum("k,kd->d", w[k0:k0 + chunk], rows)
    return out


def fold_q4_ref(acc: jax.Array, p_row: jax.Array, s_row: jax.Array,
                w, qblock: int, beta=1.0) -> jax.Array:
    """Streaming fold of one packed-q4 upload row: unpack + blockwise
    dequantize p_row (Dq//2,) int8 with s_row scales, then
    :func:`fold_ref` — the q4 accumulate-on-arrival oracle."""
    u = dequant_q4_flat_ref(p_row[None], s_row[None], qblock)[0]
    return fold_ref(acc, u, w, beta)


def fedasync_rates_flat_q4_ref(p: jax.Array, scales: jax.Array,
                               rates: jax.Array, params: jax.Array,
                               qblock: int):
    """Sequential (S, P) fedasync mix with per-row q4 dequantize in the
    fold — the q4 buffered oracle for the streaming rates channel."""
    a = rates.astype(jnp.float32)
    d = params.shape[0]

    def body(i, sp):
        s, prod = sp
        u = dequant_q4_flat_ref(p[i][None], scales[i][None], qblock)[0, :d]
        return (1.0 - a[i]) * s + a[i] * u, prod * (1.0 - a[i])

    s, prod = jax.lax.fori_loop(
        0, a.shape[0], body,
        (jnp.zeros(d, jnp.float32), jnp.float32(1.0)))
    mixed = prod * params.astype(jnp.float32) + s
    return mixed.astype(params.dtype), 1.0 - prod


def safl_agg_q4_ref(p: jax.Array, scales: jax.Array, weights: jax.Array,
                    params: jax.Array, server_lr: float,
                    qblock: int) -> jax.Array:
    """Fused unpack + dequantize + FedSGD server step oracle (q4 wire)."""
    u = dequant_q4_flat_ref(p, scales, qblock)[:, :params.shape[0]]
    return safl_agg_ref(u, weights, params, server_lr)


def weighted_avg_q4_ref(p: jax.Array, scales: jax.Array,
                        weights: jax.Array, qblock: int) -> jax.Array:
    """Fused unpack + dequantize + FedAvg weighted mean oracle (q4)."""
    return weighted_avg_ref(dequant_q4_flat_ref(p, scales, qblock), weights)


def sdga_flat_q4_ref(p: jax.Array, scales: jax.Array, staleness: jax.Array,
                     params: jax.Array, mom: jax.Array, ema: jax.Array, *,
                     qblock: int, server_lr: float, alpha: float = 0.5,
                     momentum: float = 0.8, ema_anchor: float = 0.05,
                     ema_decay: float = 0.95):
    """Fused unpack + dequantize + full SDGA round oracle (q4 wire)."""
    u = dequant_q4_flat_ref(p, scales, qblock)[:, :params.shape[0]]
    return sdga_flat_ref(u, staleness, params, mom, ema,
                         server_lr=server_lr, alpha=alpha, momentum=momentum,
                         ema_anchor=ema_anchor, ema_decay=ema_decay)


# ------------------------- top-k sparse wire -------------------------


def dequant_topk_ref(qv: jax.Array, scales: jax.Array,
                     qblock: int) -> jax.Array:
    """Blockwise-dequantize compacted top-k values.

    qv (..., nk) int8, scales (..., nk//qblock) f32 -> (..., nk) f32.
    The quantization granule runs over the *compacted* value array, not
    the dense coordinate space.  Padding blocks carry scale 0.
    """
    shp = qv.shape
    nk = shp[-1]
    q = qv.astype(jnp.float32).reshape(shp[:-1] + (nk // qblock, qblock))
    return (q * scales[..., :, None]).reshape(shp)


def topk_weighted_sum_ref(idx: jax.Array, qv: jax.Array,
                          scales: jax.Array, weights: jax.Array,
                          d: int, qblock: int) -> jax.Array:
    """sum_k w_k * scatter(dequant(qv_k), idx_k) -> (d,) f32.

    idx (K, nk) int32 coordinates into the dense (d,) row; padding
    coordinates carry idx == d and are dropped by the scatter
    (mode="drop"), so short uploads cost nothing.  The sum runs as K
    sequential row scatters so the floating-point accumulation order
    matches the streaming channel's fold-at-ingest chain on the same
    rows — the dense row is never materialized per upload.  This is the
    server's topk reduction on every backend, not only an oracle.
    """
    w = weights.astype(jnp.float32)
    vals = dequant_topk_ref(qv, scales, qblock)  # (K, nk)

    def body(k, acc):
        return acc.at[idx[k]].add(w[k] * vals[k], mode="drop")

    return jax.lax.fori_loop(0, idx.shape[0], body,
                             jnp.zeros((d,), jnp.float32))


def fold_topk_ref(acc: jax.Array, idx: jax.Array, qv: jax.Array,
                  s_row: jax.Array, w, qblock: int, beta=1.0) -> jax.Array:
    """One streaming fold of a sparse upload: acc <- beta*acc +
    w * scatter(dequant(qv), idx).  The server's topk fold on every
    backend (Pallas TPU has no scatter lowering); padding coords
    (idx == d) drop."""
    vals = dequant_topk_ref(qv, s_row, qblock)
    base = jnp.asarray(beta, jnp.float32) * acc.astype(jnp.float32)
    return base.at[idx].add(jnp.asarray(w, jnp.float32) * vals,
                            mode="drop")


def safl_agg_topk_ref(idx: jax.Array, qv: jax.Array, scales: jax.Array,
                      weights: jax.Array, params: jax.Array,
                      server_lr: float, qblock: int) -> jax.Array:
    """Fused gather-dequant-scatter + FedSGD server step oracle (topk).
    Gradient targets only: params - lr * gsum / wsum."""
    w = weights.astype(jnp.float32)
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    gsum = topk_weighted_sum_ref(idx, qv, scales, weights,
                                 params.shape[0], qblock)
    return (params.astype(jnp.float32)
            - server_lr * (gsum / wsum)).astype(params.dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """q (B,S,H,hd), k/v (B,S,Hkv,hd) GQA -> out (B,S,H,hd), f32 softmax."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ------------------- defense screening oracles (PR 8) -------------------


def screen_sumsq_ref(rows: jax.Array) -> jax.Array:
    """Fused per-row screening pass, f32 wire: (K, D) rows -> (K,) f32
    sum of squares.  NaN/Inf payload lanes surface as a non-finite sum
    (NaN^2 = NaN, Inf^2 = Inf), so ``isfinite(sumsq)`` is the whole
    integrity verdict and ``sqrt(sumsq)`` the L2 norm for cap checks —
    one reduction serves both."""
    r = rows.astype(jnp.float32)
    return jnp.sum(r * r, axis=1)


def screen_sumsq_q8_ref(q: jax.Array, scales: jax.Array,
                        qblock: int) -> jax.Array:
    """q8/topk screening: (K, Nq) int8 payload + (K, NB) f32 scales ->
    (K,) sum of squares of the dequantized row, computed blockwise
    (sum_b s_b^2 * sum_j q_j^2) without materializing the dense row.
    A ragged tail (topk's nk need not divide qblock) is zero-padded;
    an Inf/NaN scale — the catchable wire corruption — poisons the sum."""
    K, nq = q.shape
    nb = scales.shape[1]
    qf = q.astype(jnp.float32)
    pad = nb * qblock - nq
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad)))
    q2 = jnp.sum(qf.reshape(K, nb, qblock) ** 2, axis=2)
    s = scales.astype(jnp.float32)
    return jnp.sum(q2 * s * s, axis=1)


def screen_sumsq_q4_ref(p: jax.Array, scales: jax.Array,
                        qblock: int) -> jax.Array:
    """Packed-q4 screening: unpack the nibbles, then the q8 rule."""
    return screen_sumsq_q8_ref(unpack_q4_ref(p), scales, qblock)


def xor_tree_sum_ref(parts) -> jax.Array:
    """Host oracle of the intra-edge recursive-doubling tree reduce.

    ``parts`` is a length-P sequence (or a (P, ...) stacked array) of the
    per-shard partials one edge group holds.  Reproduces the EXACT
    addition pairing of :func:`repro.kernels.safl_agg.edge_partial_reduce`
    — round r adds partner ``i ^ 2**r`` — so tests can assert the mesh
    tree reduce bitwise, not just within tolerance.  Requires P to be a
    power of two (the mesh constructor enforces this for the pod
    sub-axis).
    """
    parts = [jnp.asarray(p) for p in parts]
    n = len(parts)
    assert n & (n - 1) == 0, f"pod group of {n} is not a power of two"
    shift = 1
    while shift < n:
        parts = [parts[i] + parts[i ^ shift] for i in range(n)]
        shift *= 2
    return parts[0]
