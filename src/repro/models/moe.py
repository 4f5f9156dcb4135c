"""Mixture-of-Experts layers.

``cfg.router == "softmax"``: GShard/GLaM-style dense dispatch.  TPU-native
formulation: token groups, top-k gating with per-expert capacity,
dispatch/combine einsums (pure MXU matmuls; no ragged scatter).  The expert
dimension shards over the "model" mesh axis (expert parallelism); groups shard
over batch/data.  Aux load-balance loss (Switch-style) is returned so the
train step can add it.

``cfg.router == "sigmoid"``: the DeepSeek-V3 router (``noaux_tc``) and a
dropless expert layer over the experts this chip holds
(:func:`moe_apply_dropless`).  Scores are ``sigmoid(h W_r)`` over all
``n_experts``; the top ``top_k`` of ``scores + bias`` are chosen (the bias
steers selection only); the chosen scores are normalised to sum to one
and scaled by ``routed_scaling_factor``.  The layer computes only the
held experts' share: every (token, choice) routed to a held expert is one
row of a grouped matrix product per held expert (``jax.lax.ragged_dot``),
with no capacity, so no token is dropped.  Shared experts are one SwiGLU
of ``n_shared_experts * d_ff``, added unweighted.  No auxiliary loss.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.custom_derivatives import SymbolicZero

from repro.models import layers
from repro.sharding.ctx import constrain_batch


def moe_init(key, cfg, dtype) -> dict:
    """Router over all ``n_experts``; expert weights for the held ones."""
    kg, k1, k2, k3, ks = jax.random.split(key, 5)
    D, F = cfg.d_model, cfg.d_ff
    E = cfg.held_experts
    p = {
        "router": layers.dense_init(kg, D, cfg.n_experts, jnp.float32),
        "w1": (jax.random.normal(k1, (E, D, F)) / jnp.sqrt(D)).astype(dtype),
        "w3": (jax.random.normal(k3, (E, D, F)) / jnp.sqrt(D)).astype(dtype),
        "w2": (jax.random.normal(k2, (E, F, D)) / jnp.sqrt(F)).astype(dtype),
    }
    if cfg.router == "sigmoid":
        # the selection-only bias (zero at init; updated outside training
        # in the published recipe, never by gradients)
        p["bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = layers.mlp_init(
            ks, cfg, dtype, d_ff=cfg.d_ff * cfg.n_shared_experts)
    return p


def _capacity(cfg, group_size: int) -> int:
    c = int(cfg.capacity_factor * group_size * cfg.top_k / cfg.n_experts)
    return max(4, c)


def moe_apply_scatter(params: dict, cfg, x: jax.Array):
    """Scatter/gather dispatch (§Perf): replaces the dense dispatch/combine
    einsums — whose FLOPs (2*T*E*C*D) exceed the *expert* compute by ~50x for
    kimi-k2 — with segment-sum routing (FLOP-free data movement).

    Same capacity semantics as :func:`moe_apply` (per-expert queue of C
    slots, k-priority ordering); outputs match the einsum path exactly for
    kept tokens.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    gs = min(cfg.moe_group_size, B * S)
    T = B * S
    assert T % gs == 0
    G = T // gs
    cdt = jnp.dtype(cfg.compute_dtype)
    xt = x.reshape(G, gs, D)

    logits = (xt.astype(jnp.float32) @ params["router"])  # (G, gs, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # (G, gs, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    C = _capacity(cfg, gs)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
    oh_k_major = onehot.transpose(0, 2, 1, 3).reshape(G, K * gs, E)
    pos_in_e = jnp.cumsum(oh_k_major, axis=1) - oh_k_major
    pos = jnp.einsum("gke,gke->gk", pos_in_e, oh_k_major)
    keep = pos < C
    pos = pos.reshape(G, K, gs).transpose(0, 2, 1).astype(jnp.int32)
    keep = keep.reshape(G, K, gs).transpose(0, 2, 1)
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    # flat slot id per (g, s, k): g*E*C + e*C + pos  (dropped -> overflow bin)
    slot = gate_idx * C + pos  # (G, gs, K) within group
    gidx = jnp.arange(G, dtype=jnp.int32)[:, None, None]
    flat_slot = jnp.where(keep, gidx * E * C + slot, G * E * C)
    flat_slot = flat_slot.reshape(-1)

    xk = jnp.broadcast_to(xt[:, :, None, :].astype(cdt),
                          (G, gs, K, D)).reshape(-1, D)
    expert_in = jax.ops.segment_sum(
        xk, flat_slot, num_segments=G * E * C + 1)[:-1]
    expert_in = expert_in.reshape(G, E, C, D)

    h1 = jnp.einsum("gecd,edf->gecf", expert_in, params["w1"].astype(cdt))
    h3 = jnp.einsum("gecd,edf->gecf", expert_in, params["w3"].astype(cdt))
    h = jax.nn.silu(h1) * h3
    expert_out = jnp.einsum("gecf,efd->gecd", h, params["w2"].astype(cdt))

    # combine: gather each (token, k)'s slot output, weight, sum over k
    out_flat = expert_out.reshape(G * E * C, D)
    out_flat = jnp.concatenate(
        [out_flat, jnp.zeros((1, D), out_flat.dtype)], axis=0)
    y_k = out_flat[flat_slot].reshape(G, gs, K, D)
    y = jnp.einsum("gskd,gsk->gsd", y_k, gate_vals.astype(cdt))
    y = y.reshape(B, S, D).astype(x.dtype)

    if cfg.n_shared_experts:
        y = y + layers.mlp(params["shared"], cfg, x)

    frac_tokens = jnp.mean(onehot[:, :, 0, :], axis=(0, 1))
    mean_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * mean_probs)
    return y, aux


def moe_apply(params: dict, cfg, x: jax.Array):
    """x: (B, S, D) -> (y, aux_loss)."""
    if cfg.router == "sigmoid":
        return moe_apply_dropless(params, cfg, x)
    if getattr(cfg, "moe_dispatch_impl", "einsum") == "scatter":
        return moe_apply_scatter(params, cfg, x)
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    gs = min(cfg.moe_group_size, B * S)
    T = B * S
    assert T % gs == 0, f"tokens {T} not divisible by group {gs}"
    G = T // gs
    xt = constrain_batch(x.reshape(G, gs, D))

    logits = (xt.astype(jnp.float32) @ params["router"])  # (G, gs, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # (G, gs, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    C = _capacity(cfg, gs)
    cdt = jnp.dtype(cfg.compute_dtype)
    ddt = jnp.dtype(getattr(cfg, "moe_dispatch_dtype", "float32"))
    # position of each (token, k) choice inside its expert queue.
    # The cumsum counts positions (up to gs > 256) -> must stay f32/int;
    # the big (G,gs,E,C) dispatch/combine tensors are exact 0/1 (and
    # gate-weighted) values -> built directly in compute dtype (§Perf:
    # halves the dominant MoE memory-term contribution).
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (G, gs, K, E)
    # flatten k-choices in priority order: all k=0 first, then k=1, ...
    oh_k_major = onehot.transpose(0, 2, 1, 3).reshape(G, K * gs, E)
    pos_in_e = (jnp.cumsum(oh_k_major, axis=1) - oh_k_major)  # (G, K*gs, E)
    pos = jnp.einsum("gke,gke->gk", pos_in_e, oh_k_major)  # (G, K*gs)
    keep = pos < C
    pos = pos.reshape(G, K, gs).transpose(0, 2, 1)  # (G, gs, K)
    keep = keep.reshape(G, K, gs).transpose(0, 2, 1)

    gate_vals = gate_vals * keep.astype(gate_vals.dtype)
    # dispatch (G, gs, E, C) and combine tensors
    pos_oh = jax.nn.one_hot(pos, C, dtype=ddt)  # (G, gs, K, C)
    dispatch = jnp.einsum("gske,gskc->gsec", onehot.astype(ddt),
                          pos_oh * keep[..., None].astype(ddt))
    combine = jnp.einsum("gsec,gsk,gske->gsec", dispatch,
                         gate_vals.astype(ddt), onehot.astype(ddt))

    expert_in = jnp.einsum("gsec,gsd->gecd", dispatch.astype(cdt),
                           xt.astype(cdt))  # (G, E, C, D)
    h1 = jnp.einsum("gecd,edf->gecf", expert_in, params["w1"].astype(cdt))
    h3 = jnp.einsum("gecd,edf->gecf", expert_in, params["w3"].astype(cdt))
    h = jax.nn.silu(h1) * h3
    expert_out = jnp.einsum("gecf,efd->gecd", h, params["w2"].astype(cdt))
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(cdt), expert_out)
    y = y.reshape(B, S, D).astype(x.dtype)

    if cfg.n_shared_experts:
        y = y + layers.mlp(params["shared"], cfg, x)

    # Switch aux loss: E * sum_e f_e * p_e
    frac_tokens = jnp.mean(onehot[:, :, 0, :], axis=(0, 1))  # top-1 fraction
    mean_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * mean_probs)
    return y, aux


# ---------------------------------------------------------------------------
# DeepSeek-V3 router and the dropless held-expert layer
# ---------------------------------------------------------------------------


def sigmoid_route(h, router, bias, top_k: int, scale: float):
    """``h`` (T, D) -> (chosen experts (T, k) int32, weights (T, k) f32).
    Gradients reach ``h`` through the chosen scores, not the choice."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32) @ router)
    _, sel = jax.lax.top_k(scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, sel, axis=-1)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), scale * w


def held_load(sel, held: int, offset: int):
    """Assignments to each held expert per token, (held,) f32: sums to
    the held share of the routed choices (``top_k * held / n_experts``
    at a uniform router)."""
    local = sel.reshape(-1) - offset
    counts = jnp.sum(local[:, None] == jnp.arange(held), axis=0)
    return counts.astype(jnp.float32) / sel.shape[0]


def _rdot(lhs, rhs, sizes, transpose: bool = False):
    """Grouped product of the sorted rows ``lhs`` with ``rhs`` (E, a, b)
    of their expert, or with its transpose.  On a TPU this is one
    ``ragged-dot`` kernel that visits only the rows of the groups; a
    product contracting ``rhs``'s last axis would be expanded into one
    masked dense product per expert, so a transpose is taken instead."""
    if transpose:
        rhs = jnp.swapaxes(rhs, 1, 2)
    return jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), sizes,
                              preferred_element_type=jnp.float32)


def _route_rows(sel, n_held: int, offset: int):
    """The (token, choice) slots in held-expert order: ``order`` (T*k,)
    slot of each sorted row (slots of experts this chip does not hold
    sort last), ``inv`` its inverse, ``sizes`` (n_held,) rows of each
    held expert and ``valid`` (T*k,) whether a sorted row is held."""
    local = sel.reshape(-1) - offset
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    n = order.shape[0]
    inv = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    sizes = jnp.sum(key[:, None] == jnp.arange(n_held), axis=0
                    ).astype(jnp.int32)
    valid = jnp.arange(n) < jnp.sum(held)
    return order, inv, sizes, valid, held.reshape(sel.shape)


def _held_forward_parts(x, sel, w1, w3, offset):
    k = sel.shape[1]
    order, inv, sizes, valid, held = _route_rows(sel, w1.shape[0], offset)
    rows = x[order // k]
    h1 = _rdot(rows, w1, sizes)
    h3 = _rdot(rows, w3, sizes)
    return order, inv, sizes, valid, held, h1, h3


def _held_ffn(x, sel, w, w1, w3, w2, offset):
    """(T, D) output of the held experts, and each slot's expert output
    (T, k, D) (zero where the slot's expert is held elsewhere)."""
    T, D = x.shape
    k = sel.shape[1]
    with jax.named_scope("held_experts"):
        order, inv, sizes, valid, held, h1, h3 = _held_forward_parts(
            x, sel, w1, w3, offset)
        out = _rdot(jax.nn.silu(h1) * h3, w2, sizes)
        out = jnp.where(valid[:, None], out, 0.0)  # rows past the groups
        yk = out[inv].reshape(T, k, D)
        y = jnp.einsum("tkd,tk->td", yk, jnp.where(held, w, 0.0))
    return y.astype(x.dtype), yk


def _held_backward(x, sel, w, w1, w3, w2, dy, offset):
    """Input gradients (dx, dw) of the held experts' output; no weight
    gradient (the experts are frozen)."""
    T, D = x.shape
    k = sel.shape[1]
    with jax.named_scope("held_experts"):
        order, inv, sizes, valid, held, h1, h3 = _held_forward_parts(
            x, sel, w1, w3, offset)
        g1, a1 = jax.nn.sigmoid(h1), jax.nn.silu(h1)
        out = _rdot(a1 * h3, w2, sizes)
        out = jnp.where(valid[:, None], out, 0.0)
        yk = out[inv].reshape(T, k, D)
        wh = jnp.where(held, w, 0.0)
        dy = dy.astype(jnp.float32)
        dw = jnp.einsum("tkd,td->tk", yk, dy)
        dout = (wh[:, :, None] * dy[:, None, :]).reshape(T * k, D)[order]
        dout = jnp.where(valid[:, None], dout, 0.0)
        da = _rdot(dout, w2, sizes, transpose=True)
        dh1 = da * h3 * g1 * (1.0 + h1 * (1.0 - g1))
        dh3 = da * a1
        drows = (_rdot(dh1, w1, sizes, transpose=True)
                 + _rdot(dh3, w3, sizes, transpose=True))
        drows = jnp.where(valid[:, None], drows, 0.0)
        dx = drows[inv].reshape(T, k, D).sum(axis=1)
    return dx.astype(x.dtype), jnp.where(held, dw, 0.0).astype(w.dtype)


def _lanes_merged(fn, n_tokens: int):
    """``fn`` whose first ``n_tokens`` arguments are token-major and whose
    other arguments are the expert weights, with a batching rule that
    folds the batch axis into the token axis: under ``vmap`` (the wave's
    lanes) one grouped product sees every lane's tokens.  This is exact,
    since every token's output depends on that token alone.  The weights
    are frozen and shared by every lane, never batched."""
    merged = custom_vmap(fn)

    @merged.def_vmap
    def rule(axis_size, in_batched, *args):
        tok, wts = args[:n_tokens], args[n_tokens:]
        if any(in_batched[n_tokens:]):
            raise NotImplementedError(
                "the held experts' weights are shared by every lane")
        full = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(tok, in_batched)]
        res = merged(*[a.reshape((-1,) + a.shape[2:]) for a in full], *wts)
        out = jax.tree_util.tree_map(
            lambda r: r.reshape((axis_size, -1) + r.shape[1:]), res)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return merged


@functools.lru_cache(maxsize=None)
def _held_programs(offset: int):
    fwd = _lanes_merged(
        lambda x, sel, w, w1, w3, w2: _held_ffn(x, sel, w, w1, w3, w2,
                                                offset)[0], 3)
    bwd = _lanes_merged(
        lambda x, sel, w, dy, w1, w3, w2: _held_backward(
            x, sel, w, w1, w3, w2, dy, offset), 4)

    @jax.custom_vjp
    def held(x, sel, w, w1, w3, w2):
        return fwd(x, sel, w, w1, w3, w2)

    def held_fwd(x, sel, w, w1, w3, w2):
        # the primals say which inputs are differentiated: the experts'
        # weights never are, since the backward computes no gradient for
        # them
        if any(p.perturbed for p in (w1, w3, w2)):
            raise NotImplementedError(
                "the held experts are frozen: held_experts computes no "
                "gradient of w1, w3 or w2 (train adapters over a frozen "
                "base, as transformer.lora_apply does)")
        x, sel, w, w1, w3, w2 = (p.value for p in (x, sel, w, w1, w3, w2))
        # the inputs are the residuals: the backward recomputes the rows
        # (under remat the forward's own recomputation is then dead code)
        return fwd(x, sel, w, w1, w3, w2), (x, sel, w, w1, w3, w2)

    def held_bwd(res, dy):
        x, sel, w, w1, w3, w2 = res
        if isinstance(dy, SymbolicZero):
            dy = jnp.zeros(dy.shape, dy.dtype)
        dx, dw = bwd(x, sel, w, dy, w1, w3, w2)
        return dx, None, dw, None, None, None

    held.defvjp(held_fwd, held_bwd, symbolic_zeros=True)
    return held


def held_experts(x, sel, w, w1, w3, w2, offset: int = 0):
    """``sum_{e in sel, held} w_e SwiGLU_e(x)`` per token, over the held
    experts ``offset .. offset + len(w1)``: ``x`` (T, D), ``sel`` and
    ``w`` (T, k), ``w1``/``w3`` (E, D, F), ``w2`` (E, F, D).  The
    experts are frozen: gradients reach ``x`` and ``w``, and
    differentiating ``w1``, ``w3`` or ``w2`` raises."""
    return _held_programs(int(offset))(x, sel, w, w1, w3, w2)


def moe_apply_dropless(params: dict, cfg, x: jax.Array, load: bool = False):
    """x: (B, S, D) -> (y, aux = 0), plus the held experts' load per
    token (:func:`held_load`) when ``load``."""
    B, S, D = x.shape
    h = x.reshape(B * S, D)
    sel, w = sigmoid_route(h, params["router"], params["bias"], cfg.top_k,
                           cfg.routed_scaling_factor)
    y = held_experts(h, sel, w, params["w1"], params["w3"], params["w2"],
                     cfg.held_expert_offset).reshape(B, S, D)
    if cfg.n_shared_experts:
        y = y + layers.mlp(params["shared"], cfg, x)
    aux = jnp.zeros((), jnp.float32)
    if load:
        return y, aux, held_load(sel, cfg.held_experts,
                                 cfg.held_expert_offset)
    return y, aux
