"""Composable model stacks for all assigned architecture families.

One functional API per model, built from a :class:`repro.configs.base.ModelConfig`:

    model = build_model(cfg)
    params = model.init(rng)
    loss, metrics = model.train_loss(params, batch)
    logits, cache = model.prefill(params, batch)
    logits, cache = model.decode_step(params, cache, tokens, pos)

Layer stacks are scanned (params stacked on a leading layer axis, built with
``jax.vmap`` over per-layer keys) so the lowered HLO stays compact for 512-way
SPMD dry-runs.  ``cfg.remat`` wraps scan bodies in ``jax.checkpoint``.

Families: dense | moe | vlm (decoder LMs), hybrid (Mamba2 + shared attention),
ssm (xLSTM), audio (encoder-decoder over stubbed frame embeddings).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import layers, mla as mla_lib, moe as moe_lib
from repro.models import ssm as ssm_lib, xlstm
from repro.sharding.ctx import constrain_batch


@dataclasses.dataclass
class Model:
    cfg: Any
    init: Callable
    train_loss: Callable  # (params, batch) -> (loss, metrics)
    prefill: Callable  # (params, batch) -> (logits_last, cache)
    decode_step: Callable  # (params, cache, tokens(B,), pos) -> (logits, cache)
    param_count: Callable
    # decoder LMs: (params, batch, adapters=None, load=False) ->
    # (logits, aux[, held experts' load per token, summed over layers])
    forward: Optional[Callable] = None


def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def _cdtype(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _stacked_init(fn, key, n: int):
    """vmap a per-layer init over n split keys -> params stacked on axis 0."""
    return jax.vmap(fn)(jax.random.split(key, n))


def _maybe_remat(fn, cfg):
    if cfg.remat:
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    return fn


# ===========================================================================
# Decoder layer (dense / moe)
# ===========================================================================


def _decoder_layer_init(cfg, dtype, use_moe: bool):
    def init(key):
        k1, k2 = jax.random.split(key)
        mla = cfg.attn_type == "mla"
        p = {
            "ln1": layers.rmsnorm_init(cfg.d_model, dtype),
            "attn": (mla_lib.mla_init(k1, cfg, dtype) if mla
                     else layers.attention_init(k1, cfg, dtype)),
            "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
        }
        if use_moe:
            p["moe"] = moe_lib.moe_init(k2, cfg, dtype)
        else:
            p["mlp"] = layers.mlp_init(k2, cfg, dtype, d_ff=cfg.dense_ff)
        return p
    return init


def _decoder_layer_apply(p, cfg, x, positions, window, use_moe: bool,
                         return_kv: bool = False, lora=None,
                         load: bool = False):
    """One pre-norm block -> (x, aux), or (x, aux, kv) with
    ``return_kv``, or (x, aux, held experts' load) with ``load`` (MoE
    layers of a sigmoid router).  ``lora``: this layer's adapters."""
    x = constrain_batch(x)
    h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
    kv = None
    if cfg.attn_type == "mla":
        if return_kv:
            raise NotImplementedError("no KV cache for latent attention")
        attn = mla_lib.mla_attention(p["attn"], cfg, h, positions, lora)
    else:
        attn = layers.full_attention(p["attn"], cfg, h, positions,
                                     window=window, return_kv=return_kv)
        if return_kv:
            attn, kv = attn
    x = x + attn
    h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if use_moe and load:
        y, aux, ld = moe_lib.moe_apply_dropless(p["moe"], cfg, h, load=True)
    elif use_moe:
        y, aux = moe_lib.moe_apply(p["moe"], cfg, h)
    else:
        y = layers.mlp(p["mlp"], cfg, h)
    out = constrain_batch(x + y)
    if return_kv:
        return out, aux, kv
    if use_moe and load:
        return out, aux, ld
    return out, aux


def _decoder_layer_decode(p, cfg, x, ck, cv, pos, window, use_moe: bool):
    h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
    attn, ck, cv = layers.decode_attention(p["attn"], cfg, h, ck, cv, pos,
                                           window=window)
    x = x + attn
    h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if use_moe:
        y, _ = moe_lib.moe_apply(p["moe"], cfg, h)
    else:
        y = layers.mlp(p["mlp"], cfg, h)
    return x + y, ck, cv


# ===========================================================================
# Decoder LM (dense / moe / vlm)
# ===========================================================================


def _build_decoder_lm(cfg):
    dtype = _dtype(cfg)
    is_moe = cfg.family == "moe"
    n_dense = cfg.first_k_dense if is_moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense if is_moe else 0

    def init(key):
        ke, kd, km, kh, kp = jax.random.split(key, 5)
        p = {
            "embed": layers.embed_init(ke, cfg.padded_vocab, cfg.d_model,
                                       dtype),
            "ln_f": layers.rmsnorm_init(cfg.d_model, dtype),
        }
        if n_dense:
            p["layers_dense"] = _stacked_init(
                _decoder_layer_init(cfg, dtype, False), kd, n_dense)
        if n_moe:
            p["layers_moe"] = _stacked_init(
                _decoder_layer_init(cfg, dtype, True), km, n_moe)
        if not cfg.tie_embeddings:
            p["head"] = layers.dense_init(kh, cfg.d_model, cfg.padded_vocab,
                                          dtype)
        if cfg.family == "vlm":
            p["projector"] = layers.dense_init(kp, cfg.d_model, cfg.d_model,
                                               dtype)
        return p

    def _embed_inputs(params, batch):
        cdt = _cdtype(cfg)
        x = params["embed"][batch["tokens"]].astype(cdt)
        if cfg.family == "vlm" and "prefix_embeds" in batch:
            pre = (batch["prefix_embeds"].astype(cdt)
                   @ params["projector"].astype(cdt))
            x = jnp.concatenate([pre, x], axis=1)
        return x

    def _stack(params, x, positions, window, adapters=None, load=False):
        """Run all layers via scan; returns (x, aux_sum), plus the held
        experts' load summed over the MoE layers with ``load``.
        ``adapters``: LoRA adapters stacked like ``params``' layers."""
        aux_tot = jnp.zeros((), jnp.float32)
        ld_tot = jnp.zeros((cfg.held_experts,), jnp.float32)
        for name, use_moe in (("layers_dense", False), ("layers_moe", True)):
            if name not in params:
                continue
            want = load and use_moe
            ad = adapters[name] if adapters is not None else None
            body = _maybe_remat(
                lambda carry, xs, um=use_moe, want=want:
                _decoder_layer_apply(xs[0], cfg, carry, positions, window,
                                     um, lora=xs[1], load=want), cfg)

            def scan_fn(carry, xs, body=body, want=want):
                x, aux, ld = carry
                if want:
                    x, a, l = body(x, xs)
                    ld = ld + l
                else:
                    x, a = body(x, xs)
                return (x, aux + a, ld), None

            (x, aux_tot, ld_tot), _ = jax.lax.scan(
                scan_fn, (x, aux_tot, ld_tot), (params[name], ad))
        if load:
            return x, aux_tot, ld_tot
        return x, aux_tot

    def forward(params, batch, window=None, adapters=None, load=False):
        x = _embed_inputs(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        x, aux, *ld = _stack(params, x, positions,
                             window or cfg.sliding_window, adapters, load)
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = layers.lm_head(params["embed"], params.get("head"), x,
                                cfg.tie_embeddings)
        return (logits, aux, *ld)

    def train_loss(params, batch):
        logits, aux = forward(params, batch)
        n_pre = 0
        if cfg.family == "vlm" and "prefix_embeds" in batch:
            n_pre = batch["prefix_embeds"].shape[1]
            logits = logits[:, n_pre:]
        loss = layers.cross_entropy(logits[:, :-1], batch["tokens"][:, 1:],
                                    batch.get("loss_mask"))
        total = loss + 0.01 * aux
        return total, {"loss": loss, "aux": aux}

    # ---- serving ----
    def prefill(params, batch, capacity: Optional[int] = None):
        """Single sweep: logits for the last position + a filled KV cache.

        ``capacity`` >= S reserves room for subsequent decode steps.
        """
        x = _embed_inputs(params, batch)
        B, S = x.shape[0], x.shape[1]
        capacity = max(capacity or S, S)  # must cover prefix + prompt
        positions = jnp.arange(S, dtype=jnp.int32)
        window = cfg.sliding_window
        cache = {}
        for name, use_moe in (("layers_dense", False), ("layers_moe", True)):
            if name not in params:
                continue

            def scan_fn(x, lp, um=use_moe):
                x, _, (k, v) = _decoder_layer_apply(
                    lp, cfg, x, positions, window, um, return_kv=True)
                return x, (k, v)

            x, (ks, vs) = jax.lax.scan(scan_fn, x, params[name])
            Lk = ks.shape[0]
            ck = jnp.zeros((Lk, B, capacity, cfg.n_kv_heads, cfg.hd),
                           _cdtype(cfg))
            cv = jnp.zeros_like(ck)
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, ks.astype(ck.dtype), 0, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, vs.astype(cv.dtype), 0, axis=2)
            if not cfg.scan_layers:
                # per-layer leaves: lets each decode-step DUS alias in place
                # instead of restacking the full (L,...) buffer (§Perf)
                cache[name] = {"k": tuple(ck[i] for i in range(Lk)),
                               "v": tuple(cv[i] for i in range(Lk))}
            else:
                cache[name] = {"k": ck, "v": cv}
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = layers.lm_head(params["embed"], params.get("head"),
                                x[:, -1:], cfg.tie_embeddings)
        return logits[:, 0], cache

    def decode_step(params, cache, tokens, pos, window=None):
        """tokens: (B,) int32; pos: scalar int32 absolute position.

        ``cfg.scan_layers`` False unrolls the layer loop: each layer's cache
        slice updates in place (XLA slice-donation) instead of the scan's
        ys-restacking, which rewrites the full (L, B, C, H, hd) buffer every
        iteration (864 GB/step for internvl2 decode_32k — §Perf iter. 4).
        """
        cdt = _cdtype(cfg)
        x = params["embed"][tokens][:, None, :].astype(cdt)  # (B,1,D)
        for name, use_moe in (("layers_dense", False), ("layers_moe", True)):
            if name not in params:
                continue
            if not cfg.scan_layers:
                L = jax.tree_util.tree_leaves(params[name])[0].shape[0]
                ck_all = list(cache[name]["k"])
                cv_all = list(cache[name]["v"])
                for i in range(L):
                    lp = jax.tree_util.tree_map(lambda a, i=i: a[i],
                                                params[name])
                    x, ck_all[i], cv_all[i] = _decoder_layer_decode(
                        lp, cfg, x, ck_all[i], cv_all[i], pos, window,
                        use_moe)
                cache = dict(cache)
                cache[name] = {"k": tuple(ck_all), "v": tuple(cv_all)}
                continue

            def scan_fn(carry, xs, um=use_moe):
                x = carry
                lp, ck, cv = xs
                x, ck, cv = _decoder_layer_decode(lp, cfg, x, ck, cv, pos,
                                                  window, um)
                return x, (ck, cv)

            x, (ck, cv) = jax.lax.scan(
                scan_fn, x, (params[name], cache[name]["k"],
                             cache[name]["v"]))
            cache = dict(cache)
            cache[name] = {"k": ck, "v": cv}
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = layers.lm_head(params["embed"], params.get("head"), x,
                                cfg.tie_embeddings)
        return logits[:, 0], cache

    return Model(cfg, init, train_loss, prefill, decode_step,
                 lambda p: sum(x.size for x in jax.tree_util.tree_leaves(p)),
                 forward=forward)


# ===========================================================================
# Hybrid: Mamba2 backbone + shared attention block every Nth layer (zamba2)
# ===========================================================================


def _build_hybrid(cfg):
    dtype = _dtype(cfg)
    per_group = cfg.hybrid_attn_every - 1  # mamba layers per group
    n_groups = cfg.n_layers // cfg.hybrid_attn_every

    def init(key):
        ke, km, ka, kh = jax.random.split(key, 4)
        mamba_init = lambda k: {"ln": layers.rmsnorm_init(cfg.d_model, dtype),
                                "ssm": ssm_lib.ssm_init(k, cfg, dtype)}
        grouped = jax.vmap(lambda k: _stacked_init(mamba_init, k, per_group))(
            jax.random.split(km, n_groups))
        shared = _decoder_layer_init(cfg, dtype, False)(ka)  # one copy
        return {
            "embed": layers.embed_init(ke, cfg.padded_vocab, cfg.d_model,
                                       dtype),
            "mamba": grouped,  # leaves: (n_groups, per_group, ...)
            "shared_attn": shared,
            "ln_f": layers.rmsnorm_init(cfg.d_model, dtype),
            "head": layers.dense_init(kh, cfg.d_model, cfg.padded_vocab,
                                      dtype),
        }

    def _mamba_layer(lp, x):
        h = layers.rmsnorm(lp["ln"], x, cfg.norm_eps)
        return x + ssm_lib.ssd_forward(lp["ssm"], cfg, h)

    def forward(params, batch):
        cdt = _cdtype(cfg)
        x = params["embed"][batch["tokens"]].astype(cdt)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)

        inner = _maybe_remat(lambda x, lp: (_mamba_layer(lp, x)), cfg)

        def group_fn(x, gp):
            x, _ = jax.lax.scan(lambda c, lp: (inner(c, lp), None), x, gp)
            x, _ = _decoder_layer_apply(params["shared_attn"], cfg, x,
                                        positions, None, False)
            return x, None

        x, _ = jax.lax.scan(group_fn, x, params["mamba"])
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        return layers.lm_head(params["embed"], params["head"], x, False)

    def train_loss(params, batch):
        logits = forward(params, batch)
        loss = layers.cross_entropy(logits[:, :-1], batch["tokens"][:, 1:],
                                    batch.get("loss_mask"))
        return loss, {"loss": loss}

    def prefill(params, batch, capacity: Optional[int] = None):
        """Sweep that returns last-position logits + filled SSM states and
        shared-attention KV cache."""
        cdt = _cdtype(cfg)
        x = params["embed"][batch["tokens"]].astype(cdt)
        B, S = batch["tokens"].shape
        capacity = capacity or S
        positions = jnp.arange(S, dtype=jnp.int32)

        def group_fn(x, gp):
            def mamba_fn(x, lp):
                h = layers.rmsnorm(lp["ln"], x, cfg.norm_eps)
                y, st = ssm_lib.ssd_forward(lp["ssm"], cfg, h,
                                            return_state=True)
                return x + y, st

            x, states = jax.lax.scan(mamba_fn, x, gp)
            x, _, (k, v) = _decoder_layer_apply(
                params["shared_attn"], cfg, x, positions, None, False,
                return_kv=True)
            return x, (states, k, v)

        x, (ss, ks, vs) = jax.lax.scan(group_fn, x, params["mamba"])
        ck = jnp.zeros((n_groups, B, capacity, cfg.n_kv_heads, cfg.hd), cdt)
        cache = {
            "ssm": ss,
            "k": jax.lax.dynamic_update_slice_in_dim(
                ck, ks.astype(cdt), 0, axis=2),
            "v": jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(ck), vs.astype(cdt), 0, axis=2),
        }
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = layers.lm_head(params["embed"], params["head"], x[:, -1:],
                                False)
        return logits[:, 0], cache

    def decode_step(params, cache, tokens, pos, window=None):
        cdt = _cdtype(cfg)
        x = params["embed"][tokens][:, None, :].astype(cdt)

        def group_fn(x, xs):
            gp, sstate, ck, cv = xs

            def mamba_step(carry, inp):
                x = carry
                lp, st = inp
                h = layers.rmsnorm(lp["ln"], x, cfg.norm_eps)
                y, st = ssm_lib.ssd_decode_step(lp["ssm"], cfg, h, st)
                return x + y, st

            x, sstate = jax.lax.scan(mamba_step, x, (gp, sstate))
            x, ck, cv = _decoder_layer_decode(params["shared_attn"], cfg, x,
                                              ck, cv, pos, window, False)
            return x, (sstate, ck, cv)

        x, (ss, ck, cv) = jax.lax.scan(
            group_fn, x, (params["mamba"], cache["ssm"], cache["k"],
                          cache["v"]))
        cache = {"ssm": ss, "k": ck, "v": cv}
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = layers.lm_head(params["embed"], params["head"], x, False)
        return logits[:, 0], cache

    return Model(cfg, init, train_loss, prefill, decode_step,
                 lambda p: sum(x.size for x in jax.tree_util.tree_leaves(p)))


# ===========================================================================
# xLSTM (ssm family)
# ===========================================================================


def _build_xlstm(cfg):
    dtype = _dtype(cfg)
    pat = cfg.block_pattern
    assert pat == ("mlstm", "slstm"), "xlstm stack expects alternating pairs"
    n_pairs = cfg.n_layers // 2

    def init(key):
        ke, k1, k2, kh = jax.random.split(key, 4)
        return {
            "embed": layers.embed_init(ke, cfg.padded_vocab, cfg.d_model,
                                       dtype),
            "mblocks": _stacked_init(
                lambda k: xlstm.mlstm_block_init(k, cfg, dtype), k1, n_pairs),
            "sblocks": _stacked_init(
                lambda k: xlstm.slstm_block_init(k, cfg, dtype), k2, n_pairs),
            "ln_f": layers.rmsnorm_init(cfg.d_model, dtype),
            "head": layers.dense_init(kh, cfg.d_model, cfg.padded_vocab,
                                      dtype),
        }

    def forward(params, batch):
        cdt = _cdtype(cfg)
        x = params["embed"][batch["tokens"]].astype(cdt)

        def pair_fn(x, xs):
            mp, sp = xs
            x, _ = xlstm.mlstm_block(mp, cfg, x)
            x, _ = xlstm.slstm_block(sp, cfg, x)
            return x, None

        body = _maybe_remat(lambda x, xs: pair_fn(x, xs)[0], cfg)
        x, _ = jax.lax.scan(lambda c, xs: (body(c, xs), None), x,
                            (params["mblocks"], params["sblocks"]))
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        return layers.lm_head(params["embed"], params["head"], x, False)

    def train_loss(params, batch):
        logits = forward(params, batch)
        loss = layers.cross_entropy(logits[:, :-1], batch["tokens"][:, 1:],
                                    batch.get("loss_mask"))
        return loss, {"loss": loss}

    def prefill(params, batch, capacity: Optional[int] = None):
        """Parallel-form sweep that also emits the exact recurrent states
        (closed-form for mLSTM, scan carry for sLSTM) for decode handoff."""
        cdt = _cdtype(cfg)
        x = params["embed"][batch["tokens"]].astype(cdt)

        def pair_fn(x, xs):
            mp, sp = xs
            x, mst = xlstm.mlstm_block(mp, cfg, x, return_state=True)
            x, sst = xlstm.slstm_block(sp, cfg, x)
            return x, (mst, sst)

        x, (mst, sst) = jax.lax.scan(
            pair_fn, x, (params["mblocks"], params["sblocks"]))
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = layers.lm_head(params["embed"], params["head"], x[:, -1:],
                                False)
        return logits[:, 0], {"m": mst, "s": sst}

    def decode_step(params, cache, tokens, pos, window=None):
        cdt = _cdtype(cfg)
        x = params["embed"][tokens][:, None, :].astype(cdt)

        def pair_fn(x, xs):
            mp, sp, mst, sst = xs
            x, mst = xlstm.mlstm_block(mp, cfg, x, mst, decode=True)
            x, sst = xlstm.slstm_block(sp, cfg, x, sst)
            return x, (mst, sst)

        x, (mst, sst) = jax.lax.scan(
            pair_fn, x, (params["mblocks"], params["sblocks"],
                         cache["m"], cache["s"]))
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = layers.lm_head(params["embed"], params["head"], x, False)
        return logits[:, 0], {"m": mst, "s": sst}

    return Model(cfg, init, train_loss, prefill, decode_step,
                 lambda p: sum(x.size for x in jax.tree_util.tree_leaves(p)))


# ===========================================================================
# Audio encoder-decoder (seamless backbone; frame embeddings stubbed)
# ===========================================================================


def _build_encdec(cfg):
    dtype = _dtype(cfg)
    L = cfg.n_layers
    Le = cfg.enc_layers or L

    def enc_layer_init(key):
        k1, k2 = jax.random.split(key)
        return {
            "ln1": layers.rmsnorm_init(cfg.d_model, dtype),
            "attn": layers.attention_init(k1, cfg, dtype),
            "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
            "mlp": layers.mlp_init(k2, cfg, dtype),
        }

    def dec_layer_init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "ln1": layers.rmsnorm_init(cfg.d_model, dtype),
            "attn": layers.attention_init(k1, cfg, dtype),
            "lnx": layers.rmsnorm_init(cfg.d_model, dtype),
            "xattn": layers.attention_init(k2, cfg, dtype),
            "ln2": layers.rmsnorm_init(cfg.d_model, dtype),
            "mlp": layers.mlp_init(k3, cfg, dtype),
        }

    def init(key):
        ke, k1, k2, kh = jax.random.split(key, 4)
        return {
            "embed": layers.embed_init(ke, cfg.padded_vocab, cfg.d_model,
                                       dtype),
            "enc": _stacked_init(enc_layer_init, k1, Le),
            "dec": _stacked_init(dec_layer_init, k2, L),
            "ln_enc": layers.rmsnorm_init(cfg.d_model, dtype),
            "ln_f": layers.rmsnorm_init(cfg.d_model, dtype),
            "head": layers.dense_init(kh, cfg.d_model, cfg.padded_vocab,
                                      dtype),
        }

    def encode(params, frames):
        cdt = _cdtype(cfg)
        x = frames.astype(cdt)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)

        def body(x, lp):
            h = layers.rmsnorm(lp["ln1"], x, cfg.norm_eps)
            x = x + layers.full_attention(lp["attn"], cfg, h, positions,
                                          causal=False)
            h = layers.rmsnorm(lp["ln2"], x, cfg.norm_eps)
            return x + layers.mlp(lp["mlp"], cfg, h)

        body = _maybe_remat(body, cfg)
        x, _ = jax.lax.scan(lambda c, lp: (body(c, lp), None), x,
                            params["enc"])
        return layers.rmsnorm(params["ln_enc"], x, cfg.norm_eps)

    def dec_layer(lp, x, positions, memory):
        h = layers.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        x = x + layers.full_attention(lp["attn"], cfg, h, positions)
        h = layers.rmsnorm(lp["lnx"], x, cfg.norm_eps)
        x = x + layers.full_attention(lp["xattn"], cfg, h, positions,
                                      memory=memory)
        h = layers.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        return x + layers.mlp(lp["mlp"], cfg, h)

    def forward(params, batch):
        cdt = _cdtype(cfg)
        mem = encode(params, batch["enc_frames"])
        x = params["embed"][batch["tokens"]].astype(cdt)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        body = _maybe_remat(
            lambda x, lp: dec_layer(lp, x, positions, mem), cfg)
        x, _ = jax.lax.scan(lambda c, lp: (body(c, lp), None), x,
                            params["dec"])
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        return layers.lm_head(params["embed"], params["head"], x, False)

    def train_loss(params, batch):
        logits = forward(params, batch)
        loss = layers.cross_entropy(logits[:, :-1], batch["tokens"][:, 1:],
                                    batch.get("loss_mask"))
        return loss, {"loss": loss}

    def _cross_kv(params, mem):
        """Precompute per-layer cross K/V from encoder memory."""
        B, Sm, _ = mem.shape
        hd = cfg.hd

        def one(lp):
            k = (mem @ lp["xattn"]["wk"].astype(mem.dtype)).reshape(
                B, Sm, cfg.n_kv_heads, hd)
            v = (mem @ lp["xattn"]["wv"].astype(mem.dtype)).reshape(
                B, Sm, cfg.n_kv_heads, hd)
            return k, v

        return jax.vmap(one)(params["dec"])  # (L,B,Sm,Hkv,hd)

    def prefill(params, batch, capacity: Optional[int] = None):
        cdt = _cdtype(cfg)
        mem = encode(params, batch["enc_frames"])
        x = params["embed"][batch["tokens"]].astype(cdt)
        B, S = batch["tokens"].shape
        capacity = capacity or S
        positions = jnp.arange(S, dtype=jnp.int32)

        def scan_fn(x, lp):
            h = layers.rmsnorm(lp["ln1"], x, cfg.norm_eps)
            a, (k, v) = layers.full_attention(lp["attn"], cfg, h, positions,
                                              return_kv=True)
            x = x + a
            h = layers.rmsnorm(lp["lnx"], x, cfg.norm_eps)
            x = x + layers.full_attention(lp["xattn"], cfg, h, positions,
                                          memory=mem)
            h = layers.rmsnorm(lp["ln2"], x, cfg.norm_eps)
            return x + layers.mlp(lp["mlp"], cfg, h), (k, v)

        x, (ks, vs) = jax.lax.scan(scan_fn, x, params["dec"])
        mk, mv = _cross_kv(params, mem)
        ck = jnp.zeros((L, B, capacity, cfg.n_kv_heads, cfg.hd), cdt)
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                ck, ks.astype(cdt), 0, axis=2),
            "v": jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(ck), vs.astype(cdt), 0, axis=2),
            "mk": mk, "mv": mv,
        }
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = layers.lm_head(params["embed"], params["head"], x[:, -1:],
                                False)
        return logits[:, 0], cache

    def decode_step(params, cache, tokens, pos, window=None):
        cdt = _cdtype(cfg)
        x = params["embed"][tokens][:, None, :].astype(cdt)

        def scan_fn(x, xs):
            lp, ck, cv, mk, mv = xs
            h = layers.rmsnorm(lp["ln1"], x, cfg.norm_eps)
            a, ck, cv = layers.decode_attention(lp["attn"], cfg, h, ck, cv,
                                                pos, window=window)
            x = x + a
            h = layers.rmsnorm(lp["lnx"], x, cfg.norm_eps)
            x = x + layers.cross_attention_decode(lp["xattn"], cfg, h, mk, mv)
            h = layers.rmsnorm(lp["ln2"], x, cfg.norm_eps)
            x = x + layers.mlp(lp["mlp"], cfg, h)
            return x, (ck, cv)

        x, (ck, cv) = jax.lax.scan(
            scan_fn, x, (params["dec"], cache["k"], cache["v"],
                         cache["mk"], cache["mv"]))
        cache = {"k": ck, "v": cv, "mk": cache["mk"], "mv": cache["mv"]}
        x = layers.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = layers.lm_head(params["embed"], params["head"], x, False)
        return logits[:, 0], cache

    return Model(cfg, init, train_loss, prefill, decode_step,
                 lambda p: sum(x.size for x in jax.tree_util.tree_leaves(p)))


# ===========================================================================
# entry point
# ===========================================================================


def build_model(cfg) -> Model:
    cfg.validate()
    if cfg.family in ("dense", "moe", "vlm"):
        return _build_decoder_lm(cfg)
    if cfg.family == "hybrid":
        return _build_hybrid(cfg)
    if cfg.family == "ssm":
        return _build_xlstm(cfg)
    if cfg.family == "audio":
        return _build_encdec(cfg)
    raise ValueError(cfg.family)


# ===========================================================================
# federated LoRA fine-tuning of a frozen decoder LM (the FL engine's model)
# ===========================================================================


@functools.lru_cache(maxsize=None)
def _fl_model(config: str, overrides: tuple) -> Model:
    cfg = importlib.import_module(f"repro.configs.{config}").CONFIG
    if overrides:
        cfg = dataclasses.replace(cfg, **dict(overrides))
    return build_model(cfg)


def fl_model(config: str, **overrides) -> Model:
    """The decoder LM of ``repro.configs.<config>``, with any
    ``ModelConfig`` field replaced by ``overrides`` (built once)."""
    return _fl_model(config, tuple(sorted(overrides.items())))


def lora_init(cfg, key) -> dict:
    """Rank-``cfg.lora_rank`` adapters of every decoder layer, stacked
    like the base's ``layers_dense`` / ``layers_moe``."""
    dtype = _dtype(cfg)
    n_dense = cfg.first_k_dense if cfg.family == "moe" else cfg.n_layers
    n_moe = cfg.n_layers - n_dense
    kd, km = jax.random.split(key)
    init = lambda k: mla_lib.lora_init(k, cfg, dtype)
    out = {}
    if n_dense:
        out["layers_dense"] = _stacked_init(init, kd, n_dense)
    if n_moe:
        out["layers_moe"] = _stacked_init(init, km, n_moe)
    return out


def lora_apply(params, state, x, train, *, frozen, config: str,
               **overrides):
    """The FL engine's model function for federated LoRA fine-tuning:
    ``params`` are the adapters (:func:`lora_init`), ``state`` is ``{}``
    and ``frozen`` is the base model (``build_model(cfg).init``), shared
    by every client and never trained.  ``x``: token ids (..., seq).

    Returns ``(logits (..., seq, vocab), state, report)``: ``report``
    holds the counters of an evaluation pass that the engine records on
    the round (empty in training).  A model with a sigmoid router reports
    its held experts' load there: ``routed_held``, assignments to a held
    expert per token and MoE layer (``top_k * held / n_experts`` at a
    uniform router), and ``routed_max_share``, the largest held expert's
    share of them."""
    model = fl_model(config, **overrides)
    cfg = model.cfg
    lead = x.shape[:-1]
    tokens = x.reshape(-1, x.shape[-1])
    load = (not train and cfg.family == "moe" and cfg.router == "sigmoid"
            and cfg.n_layers > cfg.first_k_dense)
    out = model.forward(frozen, {"tokens": tokens}, adapters=params,
                        load=load)
    logits = out[0][..., :cfg.vocab_size].reshape(
        lead + (x.shape[-1], cfg.vocab_size))
    report = {}
    if load:
        per = out[2] / (cfg.n_layers - cfg.first_k_dense)
        held = jnp.sum(per)
        report = {"routed_held": held,
                  "routed_max_share": jnp.max(per) / jnp.maximum(held,
                                                                 1e-30)}
    return logits, state, report
