"""Causal GQA flash attention (forward) — TPU-native online-softmax tiling.

Grid: (batch, q_heads, S/BLOCK_Q); each program owns one (BLOCK_Q, hd) query
tile in VMEM and loops over (BLOCK_K, hd) key/value tiles with the running
(m, l, acc) online-softmax state.  Causality skips fully-masked KV tiles
(the loop upper bound is derived from the q-tile index), so work per q tile
is O(q_idx) — the standard flash scheme re-blocked for MXU-friendly tile
shapes (multiples of 128 on the contracting dims).

GQA: kv head = q head // (H // Hkv), resolved in the index maps — no
repeat-kv materialization in HBM.

Forward-only by design: the serving path (prefill) is where the paper's
assigned shapes are attention-bound; training uses XLA attention (see
DESIGN.md §2).  Validated in interpret mode against ref.flash_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, hd: int,
                  causal: bool):
    qi = pl.program_id(2)
    # refs are (1, block, 1, hd) tiles; load fully and drop the unit dims —
    # integer ref indices don't survive interpret-mode state discharge
    q3 = q_ref[...].astype(jnp.float32) / np.sqrt(hd)
    bq = q3.shape[1]
    q = q3.reshape(bq, hd)  # (BLOCK_Q, hd)
    S = k_ref.shape[1]
    n_kv = S // block_k
    if causal:
        # last kv tile intersecting this q tile's causal triangle (+1)
        n_kv_live = jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k,
                                n_kv)
    else:
        n_kv_live = n_kv

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(ki, carry):
        m, l, acc = carry
        idx = (pl.dslice(0, 1), pl.dslice(ki * block_k, block_k),
               pl.dslice(0, 1), pl.dslice(0, hd))
        k = k_ref[idx].astype(jnp.float32).reshape(block_k, hd)
        v = v_ref[idx].astype(jnp.float32).reshape(block_k, hd)
        s = q @ k.T  # (BLOCK_Q, BLOCK_K)
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv_live, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-20)[:, None]
    o_ref[...] = out.reshape(1, bq, 1, hd).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = BLOCK_Q,
                    block_k: int = BLOCK_K,
                    interpret: bool = True) -> jax.Array:
    """q (B,S,H,hd), k/v (B,S,Hkv,hd) -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0

    grid = (B, H, S // block_q)
    kern = functools.partial(_flash_kernel, block_k=block_k, hd=hd,
                             causal=causal)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, hd),
                         lambda b, h, i: (b, i, h, 0)),
            pl.BlockSpec((1, S, 1, hd),
                         lambda b, h, i, _rep=rep: (b, 0, h // _rep, 0)),
            pl.BlockSpec((1, S, 1, hd),
                         lambda b, h, i, _rep=rep: (b, 0, h // _rep, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, hd),
                               lambda b, h, i: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out
