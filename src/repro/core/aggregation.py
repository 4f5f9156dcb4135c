"""Aggregation strategies (paper §3) + staleness-aware variants.

All aggregators consume a *stacked* update pytree (leading axis K = number of
buffered client updates) plus a weight vector, and return the new global
parameters.  The stacked layout is what the fused Pallas reduction kernel
(:mod:`repro.kernels.safl_agg`) accelerates on TPU; the pure-jnp path here is
its oracle and the CPU fallback.

Targets:
  * ``fedsgd`` (Eq. 4–5): gradients;  w_g ← w_g − η · Σ_i a_i ∇L_i
  * ``fedavg`` (Eq. 6):   weights;    w_g ← Σ_i (|D_i|/D) w_i
Variants (related work the paper cites + our beyond-paper SDGA):
  * ``fedasync``: w_g ← (1−α_τ) w_g + α_τ w_i       (per-update mixing)
  * ``fedbuff``:  buffered staleness-discounted gradient mean
  * ``fedopt``:   server Adam over the aggregated gradient/delta
  * ``sdga``:     staleness-damped gradient aggregation (ours) — poly
    discount + server momentum + EMA anchor toward the running weight average
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


# ---------------------------------------------------------------------------
# staleness weight functions (paper Fig. 4 motivation)
# ---------------------------------------------------------------------------


def staleness_poly(tau: jax.Array, alpha: float) -> jax.Array:
    """(1 + tau)^(-alpha) — FedAsync's polynomial discount."""
    return jnp.power(1.0 + tau.astype(jnp.float32), -alpha)


def staleness_hinge(tau: jax.Array, a: float = 4.0, b: float = 1.0) -> jax.Array:
    return jnp.where(tau <= a, 1.0, 1.0 / (b * (tau - a) + 1.0))


def staleness_const(tau: jax.Array) -> jax.Array:
    return jnp.ones_like(tau, dtype=jnp.float32)


STALENESS_FNS = {"poly": staleness_poly, "hinge": staleness_hinge,
                 "const": lambda t, alpha=0.0: staleness_const(t)}


# ---------------------------------------------------------------------------
# weighted reduction over stacked pytrees
# ---------------------------------------------------------------------------


def weighted_mean(stacked: Pytree, weights: jax.Array,
                  normalize: bool = True) -> Pytree:
    """sum_k w_k * leaf[k] / (sum_k w_k)   per leaf.

    ``stacked`` leaves have leading dim K; ``weights`` is (K,).
    """
    w = weights.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w), 1e-12) if normalize else 1.0

    def red(leaf):
        wf = w.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return (jnp.sum(leaf.astype(jnp.float32) * wf, axis=0)
                / denom).astype(leaf.dtype)

    return jax.tree_util.tree_map(red, stacked)


# ---------------------------------------------------------------------------
# aggregators
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServerOptState:
    """Server-side slow state for fedopt / sdga."""
    momentum: Pytree = None
    adam_m: Pytree = None
    adam_v: Pytree = None
    ema: Pytree = None
    step: int = 0


def fedsgd(global_params: Pytree, grads_stacked: Pytree,
           weights: jax.Array, server_lr: float) -> Pytree:
    """Eq. (4)-(5): uniform (or staleness-weighted) gradient mean + SGD."""
    g = weighted_mean(grads_stacked, weights)
    return jax.tree_util.tree_map(
        lambda p, gl: (p - server_lr * gl.astype(p.dtype)).astype(p.dtype),
        global_params, g)


def fedavg(params_stacked: Pytree, data_sizes: jax.Array) -> Pytree:
    """Eq. (6): data-size-weighted parameter average."""
    return weighted_mean(params_stacked, data_sizes.astype(jnp.float32))


def fedasync_mix(global_params: Pytree, client_params: Pytree,
                 alpha_tau: jax.Array) -> Pytree:
    return jax.tree_util.tree_map(
        lambda g, c: ((1.0 - alpha_tau) * g.astype(jnp.float32)
                      + alpha_tau * c.astype(jnp.float32)).astype(g.dtype),
        global_params, client_params)


def fedasync_coefficients(staleness: Sequence[int], fedasync_alpha: float,
                          alpha: float,
                          score: Optional[np.ndarray] = None) -> jax.Array:
    """Fold K sequential fedasync mixes into ONE buffered reduction.

    Applying p <- (1 - a_i) p + a_i w_i for i = 1..K in arrival order
    expands to p' = prod_i (1 - a_i) p + sum_i c_i w_i with

        a_i = fedasync_alpha * (1 + tau_i)^(-alpha)
        c_i = a_i * prod_{j > i} (1 - a_j)

    and the coefficients sum to 1 - prod_i (1 - a_i), so the whole
    buffered fedasync round is the single fused program
    (1 - sum(c)) p + c @ u (``mode="mix"`` in the flat kernels).  Pure
    host numpy over the host-resident staleness ints — no device sync.

    ``score`` (optional, from an adaptive scheduling policy —
    :mod:`repro.sched.policy`) multiplies each per-update mix rate a_i
    before the fold, clipped back to [0, 1] so every sequential mix
    stays a convex combination.
    """
    a = fedasync_alpha * np.power(
        1.0 + np.asarray(staleness, np.float32), -np.float32(alpha))
    if score is not None:
        a = np.clip(a * np.asarray(score, np.float32), 0.0, 1.0)
    one_minus = (1.0 - a).astype(np.float32)
    # tail_i = prod_{j>i} (1 - a_j): exclusive reversed cumprod
    tail = np.concatenate(
        [np.cumprod(one_minus[::-1])[::-1][1:], [np.float32(1.0)]])
    return jnp.asarray(a * tail, jnp.float32)


def fedbuff(global_params: Pytree, grads_stacked: Pytree,
            staleness: jax.Array, server_lr: float,
            alpha: float = 0.5) -> Pytree:
    w = staleness_poly(staleness, alpha)
    return fedsgd(global_params, grads_stacked, w, server_lr)


def fedopt_adam(global_params: Pytree, grads_stacked: Pytree,
                weights: jax.Array, opt: ServerOptState, server_lr: float,
                b1: float = 0.9, b2: float = 0.99,
                eps: float = 1e-8) -> tuple[Pytree, ServerOptState]:
    g = weighted_mean(grads_stacked, weights)
    step = opt.step + 1
    zeros = lambda: jax.tree_util.tree_map(
        lambda p: jnp.zeros_like(p, jnp.float32), global_params)
    m = opt.adam_m if opt.adam_m is not None else zeros()
    v = opt.adam_v if opt.adam_v is not None else zeros()
    m = jax.tree_util.tree_map(
        lambda mm, gg: b1 * mm + (1 - b1) * gg.astype(jnp.float32), m, g)
    v = jax.tree_util.tree_map(
        lambda vv, gg: b2 * vv + (1 - b2)
        * jnp.square(gg.astype(jnp.float32)), v, g)
    mh = jax.tree_util.tree_map(lambda mm: mm / (1 - b1 ** step), m)
    vh = jax.tree_util.tree_map(lambda vv: vv / (1 - b2 ** step), v)
    new = jax.tree_util.tree_map(
        lambda p, mm, vv: (p.astype(jnp.float32)
                           - server_lr * mm / (jnp.sqrt(vv) + eps))
        .astype(p.dtype), global_params, mh, vh)
    return new, dataclasses.replace(opt, adam_m=m, adam_v=v, step=step)


def sdga(global_params: Pytree, grads_stacked: Pytree,
         staleness: jax.Array, opt: ServerOptState, *,
         server_lr: float, alpha: float = 0.5, momentum: float = 0.8,
         ema_anchor: float = 0.05,
         ema_decay: float = 0.95) -> tuple[Pytree, ServerOptState]:
    """Staleness-Damped Gradient Aggregation (beyond-paper, DESIGN.md §3).

    FedSGD's gradient target (fast convergence) + three dampers against the
    oscillation/NaN pathologies the paper attributes to stale gradient
    directions (Fig. 4):
      1. polynomial staleness discount of each buffered gradient,
      2. server momentum (averages out direction noise across rounds),
      3. EMA anchor: a small pull toward the exponential average of past
         global weights (a FedAvg-flavoured prior that bounds excursions).
    """
    w = staleness_poly(staleness, alpha)
    g = weighted_mean(grads_stacked, w)
    zeros = lambda: jax.tree_util.tree_map(
        lambda p: jnp.zeros_like(p, jnp.float32), global_params)
    mom = opt.momentum if opt.momentum is not None else zeros()
    mom = jax.tree_util.tree_map(
        lambda mm, gg: momentum * mm + gg.astype(jnp.float32), mom, g)
    ema = opt.ema if opt.ema is not None else jax.tree_util.tree_map(
        lambda p: p.astype(jnp.float32), global_params)
    new = jax.tree_util.tree_map(
        lambda p, mm, e: (p.astype(jnp.float32) - server_lr * mm
                          + ema_anchor * (e - p.astype(jnp.float32)))
        .astype(p.dtype), global_params, mom, ema)
    ema = jax.tree_util.tree_map(
        lambda e, p: ema_decay * e + (1 - ema_decay) * p.astype(jnp.float32),
        ema, new)
    return new, dataclasses.replace(opt, momentum=mom, ema=ema,
                                    step=opt.step + 1)


# ---------------------------------------------------------------------------
# flat-buffer server program (the engine hot path)
# ---------------------------------------------------------------------------


class FlatServer:
    """One jitted, donating server round over a flat (K, D) update buffer.

    Replaces the per-leaf ``tree_map`` + ``jnp.stack`` aggregation: the
    engine keeps client updates raveled in a preallocated (K, D) device
    buffer (:mod:`repro.core.flatbuf`) and every round runs ONE compiled
    XLA program that fuses the staleness discount, the K-way weighted
    reduction, the server step (SGD / Adam / SDGA momentum+EMA) and the
    update-norm metric.  On the Pallas backends ``params`` and the slow
    server state are donated, so steady-state rounds allocate nothing (on
    the CPU oracle backend donation is skipped — see the constructor).

    Backend (see :func:`repro.kernels.safl_agg.default_backend`): the
    compiled Pallas kernels on TPU, the jnp oracle (same math, XLA-fused)
    on CPU; ``pallas_interpret`` forces the kernel bodies through the
    interpreter for validation.  The top-k wire is the exception: its
    reduction and fold are the XLA scatter on every backend (below).

    Modes: fedsgd / fedavg / fedbuff / fedopt / sdga / fedasync.  The
    weight-input vector ``wvec`` is per-mode: unit weights (fedsgd), data
    sizes (fedavg), staleness tau (fedbuff / fedopt / sdga — discounted
    in-program), or precomputed fold coefficients for fedasync
    (:func:`fedasync_coefficients` — K sequential per-update mixes as one
    unnormalized linear combination, so even the per-update aggregator
    rides the fused flat channel).  ``external_discount=True`` (set by
    the engine when an adaptive scheduling policy reweights — see
    :mod:`repro.sched.policy`) switches EVERY mode to reading ``wvec`` as
    the final precomputed reduction weights: the in-program staleness
    discount is disabled so the host-composed base-discount-times-score
    vector is applied verbatim.

    ``quantized=True`` switches the buffer input to the int8 flat channel:
    ``step`` consumes ``buf = (q int8 (K, Dq), scales f32 (K, Dq/qblock))``
    (:class:`repro.core.flatbuf.QuantBuffer` views) and the server program
    fuses blockwise dequantize into the same discount / reduction / server
    step / update-norm pass — 4x fewer HBM bytes for the K x D read that
    dominates memory-bound large-D rounds.

    ``wire`` generalizes that flag to the full wire-format ladder
    (:data:`repro.kernels.quantize.WIRES`): ``"f32"`` / ``"q8"`` keep the
    two legacy channels (``None`` defers to ``quantized``), ``"q4"``
    consumes the *packed* two-nibbles-per-byte buffer
    (``QuantBuffer(packed=True)`` views — (K, Dq/2) bytes) through the
    fused unpack-dequant kernels (:func:`safl_aggregate_q4` et al.), and
    ``"topk"`` consumes the sparse ``(idx int32 (K, nk), qv int8 (K, nk),
    scales (K, nk/qblock))`` triple (:class:`repro.core.flatbuf.TopkBuffer`
    views) through the XLA scatter-accumulate of :mod:`repro.kernels.ref`
    (:func:`~repro.kernels.ref.topk_weighted_sum_ref` /
    :func:`~repro.kernels.ref.fold_topk_ref`) on EVERY backend — Pallas
    TPU has no scatter lowering, so there is no topk kernel; the server
    still never materializes a dense (K, D) buffer.  ``topk`` is
    gradient-only: the weight-upload modes (fedavg, fedasync) are
    rejected because a sparse weight average would zero every
    untransmitted coordinate.

    ``mesh`` (a 1-D "pod" mesh, :func:`repro.sharding.flat.make_pod_mesh`,
    or the 2-D (edge, pod) mesh of
    :func:`repro.sharding.flat.make_hier_mesh`) makes the round
    multi-device: the buffer rows live sharded over the mesh row axes and
    the reduction becomes a per-shard partial weighted sum (the kernels'
    ``mode="sum"`` grid on the Pallas backends, the jnp / streaming-q8
    references on CPU) folded by the mesh-shaped collective
    (:func:`repro.sharding.flat.podwise_sums`): ONE ``psum`` over pod
    links on the 1-D mesh, or — hierarchically — log2(P) intra-edge
    ``ppermute`` tree-reduce rounds plus ONE cross-edge ``psum`` of the E
    edge partials (only E operands ever cross the slow edge boundary;
    :attr:`traffic` records the measured per-aggregation byte counts).
    The q8/q4 per-shard bodies dequantize BEFORE the tree reduce, so edge
    partials are always f32 and the 1-D tolerances carry over.  Then the
    same fused server step runs on the replicated (D,) state.  Still one
    jitted program per experiment; K must divide the mesh size.

    Streaming channel: alongside the buffered ``step`` the server compiles
    a donated **fold** program (:attr:`fold_program` — one arriving upload
    folded into a running (n_rows, D) accumulator bank row,
    :class:`repro.core.flatbuf.AccumBuffer`) and a **finalize** program
    (:meth:`finalize` — server step from the bank's partial sums + the
    natural-length ingest-weight vector, returning the bank zeroed for
    reuse).  Folding requires every upload's weight to be FINAL at ingest
    (discount-at-ingest), so the engine always builds the streaming server
    with ``external_discount=True``.  ``fedasync_rates=True`` switches
    fedasync — in BOTH channels — from the reduce-time coefficient fold
    (:func:`fedasync_coefficients`, whose reduction order cannot be
    reproduced one arrival at a time) to the foldable (S, P) form of the
    sequential mix: ``wvec`` carries the raw per-upload mix rates a_i, the
    buffered step runs :func:`repro.kernels.ref.fedasync_rates_flat_ref`,
    and the streaming channel folds with beta = 1 - a_i while the host
    tracks P = prod(1 - a_i) — the two channels are bit-exact against
    each other.
    """

    MODES = ("fedsgd", "fedavg", "fedbuff", "fedopt", "sdga", "fedasync")

    def __init__(self, mode: str, d: int, *, server_lr: float,
                 alpha: float = 0.5, momentum: float = 0.8,
                 ema_anchor: float = 0.05, ema_decay: float = 0.95,
                 b1: float = 0.9, b2: float = 0.99, eps: float = 1e-8,
                 backend: Optional[str] = None,
                 block_d: Optional[int] = None,
                 quantized: bool = False,
                 qblock: Optional[int] = None,
                 donate: Optional[bool] = None,
                 mesh=None,
                 external_discount: bool = False,
                 fedasync_rates: bool = False,
                 wire: Optional[str] = None):
        from repro.kernels import ref as _ref
        from repro.kernels import safl_agg as _k
        from repro.kernels.quantize import WIRES
        from repro.sharding import flat as _shflat

        assert mode in self.MODES, mode
        self.mode = mode
        self.d = d
        self.backend = backend or _k.default_backend()
        assert self.backend in ("pallas", "pallas_interpret", "xla")
        use_pallas = self.backend != "xla"
        interpret = self.backend == "pallas_interpret"
        bd = block_d or _k.BLOCK_D
        # ``wire`` generalizes the legacy quantized flag: None defers to
        # it (q8 when True), an explicit name wins.
        wire = wire or ("q8" if quantized else "f32")
        assert wire in WIRES, wire
        quantized = wire == "q8"
        q4 = wire == "q4"
        topk = wire == "topk"
        self.wire = wire
        self.quantized = quantized
        if topk:
            # sparse uploads only make sense for *gradient-delta* targets:
            # averaging sparse model weights would zero the untransmitted
            # coordinates instead of leaving them at the server value
            assert mode not in ("fedavg", "fedasync"), \
                f"wire='topk' is gradient-only; mode={mode} uploads weights"
        qb = qblock or _k.QBLOCK
        if (quantized or q4) and use_pallas:
            # the q8/q4 kernels read (block_d/qblock, K) scale tiles, and
            # a compiled tile needs 8 scale rows: widen the default tile
            # for coarse qblocks (the xla path has no tiling constraint)
            bd = block_d or max(bd, 8 * qb)
            if interpret:
                assert bd % qb == 0, \
                    f"block_d={bd} must be a multiple of qblock={qb}"
            else:
                _k.check_tiling(bd, qb)
        self.block_d = bd  # lanes per kernel grid step
        self.mesh = mesh if _shflat.mesh_size(mesh) > 1 else None

        # external_discount: an adaptive scheduling policy
        # (repro.sched.policy, reweights=True) precomputes the FINAL
        # reduction weights host-side (per-mode base discount x policy
        # score), so every mode — including the staleness-discounted
        # ones, in-kernel and in-oracle — reads wvec as-is.  Default
        # False keeps the jitted program identical to the pre-sched one.
        self.external_discount = external_discount
        self.fedasync_rates = fedasync_rates
        sdga_disc = "none" if external_discount else "poly"

        def discounted(wvec):
            if external_discount:
                return wvec.astype(jnp.float32)
            if mode in ("fedbuff", "fedopt", "sdga"):
                return staleness_poly(wvec, alpha)
            return wvec.astype(jnp.float32)

        n_pod = _shflat.mesh_size(self.mesh)

        def _partial_sums(buf_l, wvec_l):
            """Per-shard unnormalized weighted row sum + weight mass
            (the local body of the podwise reduction; the staleness
            discount is elementwise over K, so it applies per shard).
            Algorithm choices key on the GLOBAL row count K = K_local *
            n_pod, so the sharded round walks the same numerical path as
            the single-device one at every K."""
            w = discounted(wvec_l)
            if quantized:
                q, scales = buf_l
                if use_pallas:
                    g = _k.safl_aggregate_q8(
                        q, scales, w, mode="sum", qblock=qb, block_d=bd,
                        interpret=interpret)
                elif _ref.int8dot_auto(q.shape[0] * n_pod):
                    # large-K int8-dot (platform-gated — XLA CPU emulates
                    # int8 GEMM; see int8dot_auto): quantize this shard's
                    # reduction coefficients against the mesh-wide absmax
                    # scale — the same grid the single-device round uses
                    # (pmax spans BOTH axes of a hierarchical mesh: the
                    # regime keys on the global K)
                    cs = jax.lax.pmax(
                        _ref.int8dot_coeff_scale(scales, w),
                        _shflat.reduce_axes(self.mesh))
                    g = _ref.weighted_sum_q8_int8dot_ref(
                        q, scales, w, qb, coeff_scale=cs)
                else:
                    g = _ref.weighted_sum_q8_ref(q, scales, w, qb,
                                                 int8_dot=False)
            elif q4:
                qp, scales = buf_l
                if use_pallas:
                    g = _k.safl_aggregate_q4(
                        qp, scales, w, mode="sum", qblock=qb, block_d=bd,
                        interpret=interpret)
                else:
                    g = _ref.weighted_sum_q4_ref(qp, scales, w, qb)
            elif topk:
                idx, qv, scales = buf_l
                g = _ref.topk_weighted_sum_ref(idx, qv, scales, w, d, qb)
            elif use_pallas:
                g = _k.safl_aggregate(buf_l, w, mode="sum", block_d=bd,
                                      interpret=interpret)
            else:
                g = _ref.weighted_sum_ref(buf_l, w)
            return g, jnp.sum(w)

        pod_reduce = (_shflat.podwise_sums(
            self.mesh, _partial_sums,
            3 if topk else (2 if (quantized or q4) else 1))
                      if self.mesh is not None else None)

        #: per-aggregation cross-edge traffic (repro.sharding.flat.
        #: edge_traffic): the f32 partial each shard contributes is the
        #: unit of exchange — padded (Dq,) on the q8/q4 wires (the
        #: per-shard body dequantizes onto the qblock grid before the
        #: reduce), (d,) on f32/topk.  On a 1-D (or absent) mesh the
        #: flat and hierarchical counts coincide (reduction factor 1).
        dq = -(-d // qb) * qb
        self.traffic = _shflat.edge_traffic(
            self.mesh, 4 * (dq if (quantized or q4) else d))

        def _adam_step(p0, g, opt, params_dtype):
            step = opt["step"] + 1
            m = b1 * opt["m"] + (1 - b1) * g
            v = b2 * opt["v"] + (1 - b2) * jnp.square(g)
            sf = step.astype(jnp.float32)
            mh = m / (1 - jnp.power(b1, sf))
            vh = v / (1 - jnp.power(b2, sf))
            new = (p0 - server_lr * mh / (jnp.sqrt(vh) + eps)
                   ).astype(params_dtype)
            return new, {"m": m, "v": v, "step": step}

        def _from_sums(params, gsum, wsum, opt):
            """Server step from reduced (gsum (d,), wsum ()) — the ONE
            per-mode step body shared by the mesh buffered round, the
            streaming finalize (single-device and mesh) and, in spirit,
            the fused single-device kernels.  The op order mirrors the
            single-device references exactly (``p0 - lr * (gsum/wsafe)``,
            not ``p0 - (lr*gsum)/wsafe``) so the streaming channel is
            bit-exact against the buffered oracle."""
            p0 = params.astype(jnp.float32)
            wsafe = jnp.maximum(wsum, 1e-12)
            new_opt = opt
            if mode == "fedasync":
                # unnormalized fold: coefficients carry the mixed-in mass
                new = ((1.0 - wsum) * p0 + gsum).astype(params.dtype)
            elif mode == "fedavg":
                new = (gsum / wsafe).astype(params.dtype)
            elif mode in ("fedsgd", "fedbuff"):
                new = (p0 - server_lr * (gsum / wsafe)).astype(params.dtype)
            elif mode == "sdga":
                new, m, e = _ref.sdga_step_from_mean(
                    gsum / wsafe, params, opt["momentum"], opt["ema"],
                    server_lr=server_lr, momentum=momentum,
                    ema_anchor=ema_anchor, ema_decay=ema_decay)
                new_opt = {"momentum": m, "ema": e,
                           "step": opt["step"] + 1}
            else:  # fedopt
                new, new_opt = _adam_step(p0, gsum / wsafe, opt,
                                          params.dtype)
            return new, new_opt

        def _mesh_step(params, buf, wvec, opt):
            """Server step from the podwise-reduced (gsum, wsum) over the
            replicated (D,) state ((gsum)[:d]: q8 partials come back
            (Dq,))."""
            gsum, wsum = pod_reduce(buf, wvec)
            return _from_sums(params, gsum[:d], wsum, opt)

        def q8_mean(buf, w):
            """Discount-weighted mean over the int8 buffer -> (d,) f32.
            Streams the int8 rows (weighted_sum_q8_ref) instead of
            materializing the dequantized (K, D) f32 buffer — the CPU
            counterpart of the fused Pallas q8 kernels.  The 1/sum(w)
            normalization folds into the per-row coefficients (a (K,)
            op), so no extra pass over D."""
            q, scales = buf
            wsum = jnp.maximum(jnp.sum(w), 1e-12)
            return _ref.weighted_sum_q8_ref(q, scales, w / wsum, qb)[:d]

        def q4_mean(buf, w):
            """q8_mean's packed-int4 sibling: discount-weighted mean over
            the packed buffer -> (d,) f32, normalization folded into the
            per-row coefficients."""
            qp, scales = buf
            wsum = jnp.maximum(jnp.sum(w), 1e-12)
            return _ref.weighted_sum_q4_ref(qp, scales, w / wsum, qb)[:d]

        def _step(params, buf, wvec, opt):
            p0 = params.astype(jnp.float32)
            wmass = None
            if mode == "fedasync" and fedasync_rates:
                # foldable (S, P) form of the sequential mix: wvec is the
                # RAW per-upload rates a_i; this fori recursion is the
                # bit-exact buffered oracle of the streaming beta-folds
                # (works sharded too — GSPMD gathers the rows)
                if quantized:
                    q, scales = buf
                    new, wmass = _ref.fedasync_rates_flat_q8_ref(
                        q, scales, wvec, params, qb)
                elif q4:
                    qp, scales = buf
                    new, wmass = _ref.fedasync_rates_flat_q4_ref(
                        qp, scales, wvec, params, qb)
                else:
                    new, wmass = _ref.fedasync_rates_flat_ref(
                        buf, wvec, params)
                new_opt = opt
            elif pod_reduce is not None:
                new, new_opt = _mesh_step(params, buf, wvec, opt)
            elif topk:
                # every topk mode reduces through the one scatter-sum +
                # the shared _from_sums step body (gradient targets only)
                w = discounted(wvec)
                gsum = _ref.topk_weighted_sum_ref(*buf, w, d, qb)
                new, new_opt = _from_sums(params, gsum, jnp.sum(w), opt)
            elif mode in ("fedsgd", "fedavg", "fedbuff", "fedasync"):
                kmode = {"fedavg": "avg", "fedasync": "mix"}.get(mode,
                                                                 "fedsgd")
                disc = ("poly" if mode == "fedbuff"
                        and not external_discount else "none")
                if use_pallas and quantized:
                    q, scales = buf
                    new = _k.safl_aggregate_q8(
                        q, scales, wvec,
                        None if mode == "fedavg" else params,
                        server_lr=server_lr, mode=kmode, qblock=qb,
                        block_d=bd, interpret=interpret, alpha=alpha,
                        discount=disc)
                    if mode == "fedavg":
                        new = new[:d]
                elif use_pallas and q4:
                    qp, scales = buf
                    new = _k.safl_aggregate_q4(
                        qp, scales, wvec,
                        None if mode == "fedavg" else params,
                        server_lr=server_lr, mode=kmode, qblock=qb,
                        block_d=bd, interpret=interpret, alpha=alpha,
                        discount=disc)
                    if mode == "fedavg":
                        new = new[:d]
                elif use_pallas:
                    new = _k.safl_aggregate(
                        buf, wvec, None if mode == "fedavg" else params,
                        server_lr=server_lr, mode=kmode, block_d=bd,
                        interpret=interpret, alpha=alpha, discount=disc)
                elif quantized:
                    if mode == "fedasync":
                        # unnormalized fold: the coefficients already sum
                        # to the total mixed-in mass
                        q, scales = buf
                        g = _ref.weighted_sum_q8_ref(
                            q, scales, wvec.astype(jnp.float32), qb)[:d]
                        new = ((1.0 - jnp.sum(wvec.astype(jnp.float32)))
                               * p0 + g).astype(params.dtype)
                    else:
                        g = q8_mean(buf, discounted(wvec))
                        if mode == "fedavg":
                            new = g
                        else:
                            new = (p0 - server_lr * g).astype(params.dtype)
                elif q4:
                    if mode == "fedasync":
                        qp, scales = buf
                        g = _ref.weighted_sum_q4_ref(
                            qp, scales, wvec.astype(jnp.float32), qb)[:d]
                        new = ((1.0 - jnp.sum(wvec.astype(jnp.float32)))
                               * p0 + g).astype(params.dtype)
                    else:
                        g = q4_mean(buf, discounted(wvec))
                        if mode == "fedavg":
                            new = g
                        else:
                            new = (p0 - server_lr * g).astype(params.dtype)
                else:
                    w = discounted(wvec)
                    if mode == "fedasync":
                        new = _ref.fedasync_flat_ref(buf, w, params)
                    elif mode == "fedavg":
                        new = _ref.weighted_avg_ref(buf, w)
                    else:
                        new = _ref.safl_agg_ref(buf, w, params, server_lr)
                new_opt = opt
            elif mode == "sdga":
                if use_pallas and quantized:
                    q, scales = buf
                    new, m, e = _k.sdga_aggregate_q8(
                        q, scales, wvec, params, opt["momentum"],
                        opt["ema"], server_lr=server_lr, alpha=alpha,
                        momentum=momentum, ema_anchor=ema_anchor,
                        ema_decay=ema_decay, qblock=qb, block_d=bd,
                        interpret=interpret, discount=sdga_disc)
                elif use_pallas and q4:
                    qp, scales = buf
                    new, m, e = _k.sdga_aggregate_q4(
                        qp, scales, wvec, params, opt["momentum"],
                        opt["ema"], server_lr=server_lr, alpha=alpha,
                        momentum=momentum, ema_anchor=ema_anchor,
                        ema_decay=ema_decay, qblock=qb, block_d=bd,
                        interpret=interpret, discount=sdga_disc)
                elif use_pallas:
                    new, m, e = _k.sdga_aggregate(
                        buf, wvec, params, opt["momentum"], opt["ema"],
                        server_lr=server_lr, alpha=alpha, momentum=momentum,
                        ema_anchor=ema_anchor, ema_decay=ema_decay,
                        block_d=bd, interpret=interpret,
                        discount=sdga_disc)
                elif quantized or q4:
                    # the shared SDGA step over the streaming q8/q4 mean
                    g = (q8_mean if quantized else q4_mean)(
                        buf, discounted(wvec))
                    new, m, e = _ref.sdga_step_from_mean(
                        g, params, opt["momentum"], opt["ema"],
                        server_lr=server_lr, momentum=momentum,
                        ema_anchor=ema_anchor, ema_decay=ema_decay)
                elif external_discount:
                    # the reference discounts in-fn; the external-weight
                    # path takes the mean with wvec as-is and shares the
                    # SDGA step (the same split the q8 branch uses)
                    w = wvec.astype(jnp.float32)
                    g = (_ref.weighted_sum_ref(buf, w)
                         / jnp.maximum(jnp.sum(w), 1e-12))
                    new, m, e = _ref.sdga_step_from_mean(
                        g, params, opt["momentum"], opt["ema"],
                        server_lr=server_lr, momentum=momentum,
                        ema_anchor=ema_anchor, ema_decay=ema_decay)
                else:
                    new, m, e = _ref.sdga_flat_ref(
                        buf, wvec, params, opt["momentum"],
                        opt["ema"],
                        server_lr=server_lr, alpha=alpha, momentum=momentum,
                        ema_anchor=ema_anchor, ema_decay=ema_decay)
                new_opt = {"momentum": m, "ema": e,
                           "step": opt["step"] + 1}
            else:  # fedopt: server Adam over the discounted gradient mean
                w = discounted(wvec)
                if quantized:
                    g = q8_mean(buf, w)
                elif q4:
                    g = q4_mean(buf, w)
                else:
                    wsum = jnp.maximum(jnp.sum(w), 1e-12)
                    g = jnp.einsum("k,kd->d", w,
                                   buf.astype(jnp.float32)) / wsum
                new, new_opt = _adam_step(p0, g, opt, params.dtype)
            upd = new.astype(jnp.float32) - p0
            metrics = {"update_norm": jnp.sqrt(jnp.sum(jnp.square(upd))),
                       "weight_sum": (jnp.sum(discounted(wvec))
                                      if wmass is None else wmass)}
            return new, new_opt, metrics

        # donate params + slow state on the compiled-kernel backends, where
        # in-place rounds keep HBM residency flat.  On the CPU oracle
        # backend donation is a measured pessimization: aliasing the output
        # onto the donated params forces XLA to split the fused step (the
        # update-norm metric still reads the pre-step params), costing
        # extra full-D round-trips per round.  Callers that keep references
        # to past params (the horizon-batched SAFL engine hands the current
        # flat global model to refreshing clients) must pass donate=False —
        # donation invalidates the buffer even while it is still referenced.
        if donate is None:
            donate = use_pallas
        self._fn = jax.jit(_step, donate_argnums=(0, 3) if donate else ())

        # ---- streaming channel: fold-on-arrival + finalize programs ----
        # Only fedasync folds with a live beta (= 1 - a_i); the sum modes
        # pass the CONSTANT 1.0 default so XLA elides the accumulator
        # multiply — a traced beta=1.0 changes how LLVM contracts the
        # mul+add into FMAs and breaks the fold-chain == einsum bitwise
        # parity the streaming channel promises.
        fold_beta = mode == "fedasync"
        if quantized:
            def _fold(bank, q_row, s_row, ridx, w, beta):
                row = jax.lax.dynamic_slice(
                    bank, (ridx, jnp.int32(0)), (1, bank.shape[1]))[0]
                if use_pallas:
                    folded = _k.safl_fold_q8(
                        row, q_row, s_row, w, beta if fold_beta else 1.0,
                        qblock=qb, block_d=bd, interpret=interpret)
                elif fold_beta:
                    folded = _ref.fold_q8_ref(row, q_row, s_row, w, qb,
                                              beta)
                else:
                    folded = _ref.fold_q8_ref(row, q_row, s_row, w, qb)
                return jax.lax.dynamic_update_slice(
                    bank, folded[None], (ridx, jnp.int32(0)))
        elif q4:
            def _fold(bank, p_row, s_row, ridx, w, beta):
                row = jax.lax.dynamic_slice(
                    bank, (ridx, jnp.int32(0)), (1, bank.shape[1]))[0]
                if use_pallas:
                    folded = _k.safl_fold_q4(
                        row, p_row, s_row, w, beta if fold_beta else 1.0,
                        qblock=qb, block_d=bd, interpret=interpret)
                elif fold_beta:
                    folded = _ref.fold_q4_ref(row, p_row, s_row, w, qb,
                                              beta)
                else:
                    folded = _ref.fold_q4_ref(row, p_row, s_row, w, qb)
                return jax.lax.dynamic_update_slice(
                    bank, folded[None], (ridx, jnp.int32(0)))
        elif topk:
            # topk is gradient-only (no fedasync), so beta is always the
            # constant 1.0 — the scatter-accumulate never decays the bank
            def _fold(bank, idx_row, qv_row, s_row, ridx, w, beta):
                row = jax.lax.dynamic_slice(
                    bank, (ridx, jnp.int32(0)), (1, bank.shape[1]))[0]
                folded = _ref.fold_topk_ref(row, idx_row, qv_row, s_row, w,
                                            qb)
                return jax.lax.dynamic_update_slice(
                    bank, folded[None], (ridx, jnp.int32(0)))
        else:
            def _fold(bank, vec, ridx, w, beta):
                row = jax.lax.dynamic_slice(
                    bank, (ridx, jnp.int32(0)), (1, bank.shape[1]))[0]
                if use_pallas:
                    folded = _k.safl_fold(
                        row, vec, w, beta if fold_beta else 1.0,
                        block_d=bd, interpret=interpret)
                elif fold_beta:
                    # fedasync: beta == 1 - w, in the buffered oracle's
                    # explicit one-product order
                    del beta
                    folded = _ref.mix_ref(row, vec, w)
                else:
                    folded = _ref.fold_ref(row, vec, w)
                return jax.lax.dynamic_update_slice(
                    bank, folded[None], (ridx, jnp.int32(0)))

        #: jitted donated fold: (bank, *payload, ridx, w, beta) -> bank
        #: with bank[ridx] <- beta*bank[ridx] + w*payload, in place.  The
        #: row index and both scalars are traced, so every upload of a
        #: run reuses ONE compiled program (the one-compile guard —
        #: :attr:`fold_compile_count`).  Payload is (vec,) f32,
        #: (q_row, s_row) on the q8/q4 channels, or the sparse
        #: (idx_row, qv_row, s_row) triple on topk.
        if self.mesh is not None:
            # one bank row per mesh shard, folded where it lives
            _fold = _shflat.rowwise_fold(self.mesh, _fold)
        self.fold_program = jax.jit(_fold, donate_argnums=(0,))

        pod_bank_reduce = (_shflat.podwise_bank_sums(self.mesh)
                           if self.mesh is not None else None)

        def _finalize(params, bank, wvec, opt, pprod):
            p0 = params.astype(jnp.float32)
            if mode == "fedasync":
                assert fedasync_rates, \
                    "streaming fedasync requires fedasync_rates=True"
                # rates always fold into row 0; P = prod(1 - a_i) is
                # tracked host-side (bit-equal to the in-program product)
                gsum = bank[0][:d]
                new = (pprod * p0 + gsum).astype(params.dtype)
                new_opt = opt
                wsum = 1.0 - pprod
            elif pod_bank_reduce is not None:
                gsum, wsum = pod_bank_reduce(bank, wvec)
                new, new_opt = _from_sums(params, gsum[:d], wsum, opt)
            else:
                # sum(w) over the NATURAL-length weight vector: the same
                # reduction tree the buffered step runs over its (K,)
                # wvec, which is what keeps finalize bit-exact against it
                gsum = bank[0][:d]
                wsum = jnp.sum(wvec.astype(jnp.float32))
                new, new_opt = _from_sums(params, gsum, wsum, opt)
            upd = new.astype(jnp.float32) - p0
            metrics = {"update_norm": jnp.sqrt(jnp.sum(jnp.square(upd))),
                       "weight_sum": wsum}
            zeroed = jnp.zeros_like(bank)
            if self.mesh is not None:
                # keep the zeroed bank's rows on their shards (unpinned,
                # the partitioner may hand back a replicated bank)
                zeroed = jax.lax.with_sharding_constraint(
                    zeroed, _shflat.row_sharding(self.mesh))
            return new, new_opt, metrics, zeroed

        # the bank is always donated: the fused zero-after-read output
        # reuses its memory, which is what AccumBuffer.release recycles
        self._finalize_fn = jax.jit(
            _finalize,
            donate_argnums=(1,) + ((0, 3) if donate else ()))

        # ---- defense screening: fused per-row isfinite + L2 (PR 8) ----
        # One sum of squares per row of the wire payload (dequantized for
        # the lossy wires, computed blockwise without materializing the
        # dense row).  NaN/Inf lanes — or a corrupted scale — poison the
        # sum, so isfinite(sumsq) is the integrity verdict and
        # sqrt(sumsq) the L2 norm for cap checks.  Row-independent
        # reductions, so the single-upload (K=1) and wave-stacked calls
        # agree bitwise — the channel-parity invariant.
        if quantized or q4:
            def _screen(qrows, scales):
                if use_pallas:
                    fn = (_k.screen_rows_q8 if quantized
                          else _k.screen_rows_q4)
                    return fn(qrows, scales, qblock=qb, block_d=bd,
                              interpret=interpret)
                fn = (_ref.screen_sumsq_q8_ref if quantized
                      else _ref.screen_sumsq_q4_ref)
                return fn(qrows, scales, qb)
        elif topk:
            def _screen(idx, qv, scales):
                del idx  # integrity lives in the value/scale lanes
                if use_pallas:
                    return _k.screen_rows_q8(qv, scales, qblock=qb,
                                             block_d=bd,
                                             interpret=interpret)
                return _ref.screen_sumsq_q8_ref(qv, scales, qb)
        else:
            def _screen(rows):
                if use_pallas:
                    return _k.screen_rows(rows, block_d=bd,
                                          interpret=interpret)
                return _ref.screen_sumsq_ref(rows)
        self._screen_fn = jax.jit(_screen)

    def screen(self, payload) -> jax.Array:
        """(K,) f32 sums of squares of the K payload rows, on the wire's
        native format (``payload`` = the same tuple the step/fold
        consume: ``(rows,)`` f32, ``(q, scales)`` q8/q4, ``(idx, qv,
        scales)`` topk).  Per-row reductions are K-independent, so the
        sequential engine's K=1 call and the batched wave call return
        bitwise-identical values for the same row."""
        return self._screen_fn(*payload)

    def init_opt(self, params_flat: jax.Array):
        """Mode-matched slow state (flat f32 vectors, donated each round)."""
        z = lambda: jnp.zeros((self.d,), jnp.float32)
        if self.mode == "sdga":
            # explicit copy: params and opt are donated separately, so the
            # EMA must not alias the params buffer (f32 astype is a no-op)
            return {"momentum": z(),
                    "ema": jnp.array(params_flat, jnp.float32, copy=True),
                    "step": jnp.zeros((), jnp.int32)}
        if self.mode == "fedopt":
            return {"m": z(), "v": z(), "step": jnp.zeros((), jnp.int32)}
        return {}

    def step(self, params_flat, buf, wvec, opt):
        """(D,) params, buffer, (K,) weight-input, opt ->
        (new params, new opt, {update_norm, weight_sum}).

        ``buf`` is the f32 (K, D) buffer, or — with ``quantized=True`` —
        the ``(q int8 (K, Dq), scales (K, Dq/qblock))`` pair."""
        return self._fn(params_flat, buf, wvec, opt)

    def finalize(self, params_flat, bank, wvec, opt, pprod=1.0):
        """Streaming server round from a sealed accumulator bank.

        ``bank`` (n_rows, D) f32 partial sums (DONATED — consume the
        returned zeroed bank via ``AccumBuffer.release``), ``wvec`` the
        horizon's ingest weights in arrival order (natural length — one
        finalize compilation per distinct horizon size; queue/k horizons
        see exactly one), ``pprod`` the host-tracked fedasync survival
        product (ignored by the other modes).  Returns
        ``(new_params, new_opt, {update_norm, weight_sum}, zeroed_bank)``.
        """
        return self._finalize_fn(params_flat, bank,
                                 jnp.asarray(wvec, jnp.float32), opt,
                                 jnp.float32(pprod))

    @property
    def compile_count(self) -> int:
        """Number of XLA compilations of the server program (the recompile
        guard: must stay 1 across rounds).  Counts whichever channel ran:
        the buffered step if it ever compiled, else the max over the
        streaming fold / finalize programs."""
        n = self._fn._cache_size()
        if n > 0:
            return n
        return max(self.fold_program._cache_size(),
                   self._finalize_fn._cache_size())

    @property
    def fold_compile_count(self) -> int:
        """Compilations of the streaming fold program alone (must stay 1
        across every upload of a run — ridx/w/beta are traced)."""
        return self.fold_program._cache_size()


# ---------------------------------------------------------------------------
# mesh-level FL step (the technique as a first-class pjit feature)
# ---------------------------------------------------------------------------


def podwise_aggregate(stacked: Pytree, weights: jax.Array,
                      target: str, global_params: Optional[Pytree] = None,
                      server_lr: float = 1.0) -> Pytree:
    """Aggregation across the leading "clients" axis of a pod-stacked pytree
    inside a jit program.  With the leading dim sharded over the mesh "pod"
    axis, XLA lowers the mean to an all-reduce over pod links — the paper's
    server round, expressed as a collective.

    This pytree form is the didactic sketch; the engine hot path runs the
    same idea over the flat (K, D) channel for every mode x {f32, q8} —
    ``FlatServer(mesh=...)`` + :func:`repro.sharding.flat.podwise_sums`
    (per-shard ``mode="sum"`` kernel partials + one psum).

    target == "grads":  FedSGD (requires global_params)
    target == "params": FedAvg
    """
    if target == "grads":
        assert global_params is not None
        return fedsgd(global_params, stacked, weights, server_lr)
    return weighted_mean(stacked, weights)
