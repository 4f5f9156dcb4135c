"""Multi-head latent attention (DeepSeek-V2/V3, arXiv:2405.04434) with
optional low-rank adapters on its four projections.

Per token ``x`` (no query latent, ``q_lora_rank`` null as in Moonlight):

* ``q = x W_q``, split per head into ``q_nope`` (``qk_nope_head_dim``)
  and ``q_pe`` (``qk_rope_head_dim``);
* ``[c_kv, k_pe] = x W_kva`` (``kv_lora_rank`` + ``qk_rope_head_dim``),
  then ``c_kv = RMSNorm(c_kv)``;
* ``[k_nope, v]`` per head ``= c_kv W_kvb``;
* rope on ``q_pe`` and on the one ``k_pe`` every head shares;
* scores ``(q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)``,
  causal softmax, ``o = (P v) W_o``.

Rope rotates split halves (:func:`repro.models.layers.apply_rope`); the
published checkpoints' interleaved layout is a fixed permutation of the
rope columns of ``q_proj`` and ``kv_a_proj_with_mqa``.

A projection named in ``cfg.lora_targets`` adds ``(alpha / r) (x A) B``
when its adapter is given (:func:`lora_init`: ``A ~ N(0, 1/in)``,
``B = 0``, so a fresh adapter leaves the layer as it is).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers


def _shapes(cfg) -> dict:
    """(in, out) of each projection, by its published name."""
    h, d = cfg.n_heads, cfg.d_model
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        "q_proj": (d, h * qk),
        "kv_a_proj_with_mqa": (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_b_proj": (cfg.kv_lora_rank,
                      h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o_proj": (h * cfg.v_head_dim, d),
    }


def mla_init(key, cfg, dtype) -> dict:
    shapes = _shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    p = {name: layers.dense_init(k, a, b, dtype)
         for k, (name, (a, b)) in zip(keys, shapes.items())}
    p["kv_a_norm"] = layers.rmsnorm_init(cfg.kv_lora_rank, dtype)
    return p


def lora_init(key, cfg, dtype) -> dict:
    """One layer's adapters on ``cfg.lora_targets``."""
    shapes = _shapes(cfg)
    keys = jax.random.split(key, len(cfg.lora_targets))
    out = {}
    for k, name in zip(keys, cfg.lora_targets):
        a, b = shapes[name]
        out[name] = {
            "a": (jax.random.normal(k, (a, cfg.lora_rank))
                  / np.sqrt(a)).astype(dtype),
            "b": jnp.zeros((cfg.lora_rank, b), dtype)}
    return out


def _proj(p: dict, lora: Optional[dict], name: str, cfg, x):
    y = x @ p[name].astype(x.dtype)
    if lora is not None and name in lora:
        ad = lora[name]
        y = y + (cfg.lora_alpha / cfg.lora_rank) * (
            (x @ ad["a"].astype(x.dtype)) @ ad["b"].astype(x.dtype))
    return y


def mla_attention(p: dict, cfg, x, positions, lora: Optional[dict] = None):
    """Causal self-attention of ``x`` (B, S, D) -> (B, S, D)."""
    B, S, _ = x.shape
    H, nope = cfg.n_heads, cfg.qk_nope_head_dim
    rope, vd = cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla_attention"):
        q = _proj(p, lora, "q_proj", cfg, x).reshape(B, S, H, nope + rope)
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        kva = _proj(p, lora, "kv_a_proj_with_mqa", cfg, x)
        c_kv = layers.rmsnorm(p["kv_a_norm"], kva[..., :cfg.kv_lora_rank],
                              cfg.norm_eps)
        k_pe = kva[..., cfg.kv_lora_rank:][:, :, None, :]  # (B, S, 1, rope)
        kv = _proj(p, lora, "kv_b_proj", cfg, c_kv).reshape(B, S, H,
                                                              nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_pe = layers.apply_rope(q_pe, positions, cfg.rope_theta)
        k_pe = layers.apply_rope(k_pe, positions, cfg.rope_theta)[:, :, 0]
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe,
                               preferred_element_type=jnp.float32))
        scores = scores / np.sqrt(nope + rope)
        causal = positions[:, None] >= positions[None, :]
        scores = jnp.where(causal[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * vd)
        return _proj(p, lora, "o_proj", cfg, o)
