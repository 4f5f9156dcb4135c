"""Moonlight-16B-A3B cut to one chip's share of an 8-chip expert-parallel
layer, fine-tuned by LoRA over a frozen base, for the benchmark: token
data and weights from a seed, the plain reference forward pass, and the
model FLOPs from shapes.

Nothing here imports the program.  ``init`` lays the base and the
adapters out in the dict layout the program's ``lora_apply`` reads:
``embed``, ``head``, ``ln_f`` and the layer stacks ``layers_dense`` (1)
and ``layers_moe`` (4), each leaf with a leading layer axis.

The layer equations (DeepSeek-V3, arXiv:2412.19437; MLA, arXiv:2405.04434),
every width as published (``bench/configs/moonlight-16b-a3b-ep8.json``):

* pre-norm blocks, RMSNorm ``x / sqrt(mean(x^2) + eps) * scale``;
* attention: ``q = x W_q`` split per head into ``q_nope`` (128) and
  ``q_pe`` (64); ``[c_kv (512), k_pe (64)] = x W_kva``, ``c_kv`` RMS-normed;
  ``[k_nope (128), v (128)]`` per head ``= c_kv W_kvb``; rope (theta
  50,000, split halves) on ``q_pe`` and on the one ``k_pe`` all heads
  share; scores ``(q_nope . k_nope + q_pe . k_pe) / sqrt(192)``, causal
  softmax, ``o = (P v) W_o``;
* LoRA on those four projections: ``y = x W + (alpha / r) (x A) B``;
* dense layer: SwiGLU of width 11,264;
* MoE layers: scores ``s = sigmoid(h W_r)`` over all 64 experts; the top
  6 of ``s + b`` (``b`` the selection-only bias) chosen; weights ``w =
  2.446 s_sel / (sum s_sel + 1e-20)``; output ``sum over chosen held e of
  w_e SwiGLU_e(h)`` over the 8 held experts (computed densely for every
  token and masked), plus one SwiGLU of width 2 x 1,408 (the shared
  experts), unweighted;
* head: logits over the chip's 20,480 ids.

Departures from the published model, each noted in the JSON's
``assumed``: split-halves rope, a zero and frozen selection bias, no
auxiliary loss, a random base.
"""
from __future__ import annotations

import numpy as np


def _n_moe(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def _proj_shapes(cfg) -> dict:
    h, d = cfg["num_attention_heads"], cfg["hidden_size"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return {
        "q_proj": (d, h * (nope + rope)),
        "kv_a_proj_with_mqa": (d, cfg["kv_lora_rank"] + rope),
        "kv_b_proj": (cfg["kv_lora_rank"],
                      h * (nope + cfg["v_head_dim"])),
        "o_proj": (h * cfg["v_head_dim"], d),
    }


def make_data(cfg, traffic, key):
    """Uniform random token ids over the chip's slice; ``ys`` is ``xs``
    shifted by one position (the next id), both (clients, batches,
    batch, seq_len) int32."""
    import jax
    import jax.numpy as jnp

    pop = traffic["population"]
    n, per = int(pop["n_clients"]), int(pop["samples_per_client"])
    b = int(traffic["engine"]["local_batch_size"])
    n_eval, t = int(traffic["eval_samples"]), int(cfg["seq_len"])
    k1, k2 = jax.random.split(key)
    seq = jax.random.randint(k1, (n * per, t + 1), 0, cfg["vocab_size"],
                             jnp.int32)
    test = jax.random.randint(k2, (n_eval, t + 1), 0, cfg["vocab_size"],
                              jnp.int32)
    seq, test = jax.device_get((seq, test))
    return dict(xs=seq[:, :-1].reshape(n, per // b, b, t),
                ys=seq[:, 1:].reshape(n, per // b, b, t),
                test_x=test[:, :-1], test_y=test[:, 1:])


def init(cfg, key):
    """(adapters, {}, base).  Jittable."""
    import jax
    import jax.numpy as jnp

    d, v = cfg["hidden_size"], cfg["vocab_size"]
    f, e = cfg["moe_intermediate_size"], cfg["n_experts_held"]
    fs = f * cfg["n_shared_experts"]
    r = cfg["lora_rank"]
    keys = iter(jax.random.split(key, 64))

    def normal(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.float32(np.sqrt(fan_in)))

    def ones(*shape):
        return {"scale": jnp.ones(shape, jnp.float32)}

    def attn(n):
        out = {name: normal((n, a, b), a)
               for name, (a, b) in _proj_shapes(cfg).items()}
        out["kv_a_norm"] = ones(n, cfg["kv_lora_rank"])
        return out

    def swiglu(lead, width):
        return {"w1": normal(lead + (d, width), d),
                "w3": normal(lead + (d, width), d),
                "w2": normal(lead + (width, d), width)}

    n_d, n_m = cfg["first_k_dense_replace"], _n_moe(cfg)
    base = {
        "embed": normal((v, d), 1),
        "head": normal((d, v), d),
        "ln_f": ones(d),
        "layers_dense": {"ln1": ones(n_d, d), "attn": attn(n_d),
                         "ln2": ones(n_d, d),
                         "mlp": swiglu((n_d,), cfg["intermediate_size"])},
        "layers_moe": {"ln1": ones(n_m, d), "attn": attn(n_m),
                       "ln2": ones(n_m, d),
                       "moe": {"router": normal(
                                   (n_m, d, cfg["n_routed_experts"]), d),
                               "bias": jnp.zeros(
                                   (n_m, cfg["n_routed_experts"]),
                                   jnp.float32),
                               **swiglu((n_m, e), f),
                               "shared": swiglu((n_m,), fs)}},
    }

    def adapters(n):
        return {name: {"a": normal((n, a, r), a),
                       "b": jnp.zeros((n, r, b), jnp.float32)}
                for name, (a, b) in _proj_shapes(cfg).items()
                if name in cfg["lora_targets"]}

    return ({"layers_dense": adapters(n_d), "layers_moe": adapters(n_m)},
            {}, base)


def _rms(x, scale, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(x.dtype)


def _rope(x, theta):
    """Split-halves rotation of ``x`` (B, S, ..., d) by position."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(d // 2) / (d // 2))
    ang = np.arange(s)[:, None] * freq[None, :]
    shape = (1, s) + (1,) * (x.ndim - 3) + (d // 2,)
    cos = jnp.asarray(np.cos(ang).reshape(shape), x.dtype)
    sin = jnp.asarray(np.sin(ang).reshape(shape), x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(cfg, p, ad, x):
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    h = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, c = cfg["v_head_dim"], cfg["kv_lora_rank"]
    scale = cfg["lora_alpha"] / cfg["lora_rank"]

    def proj(name, inp):
        y = inp @ p[name].astype(inp.dtype)
        if name in ad:
            y = y + scale * ((inp @ ad[name]["a"].astype(inp.dtype))
                             @ ad[name]["b"].astype(inp.dtype))
        return y

    q = proj("q_proj", x).reshape(b, s, h, nope + rope)
    kva = proj("kv_a_proj_with_mqa", x)
    c_kv = _rms(kva[..., :c], p["kv_a_norm"]["scale"], cfg["rms_norm_eps"])
    k_pe = _rope(kva[..., c:], cfg["rope_theta"])  # (b, s, rope)
    kv = proj("kv_b_proj", c_kv).reshape(b, s, h, nope + vd)
    q_pe = _rope(q[..., nope:], cfg["rope_theta"])
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe))
    scores = scores / float(np.sqrt(nope + rope))
    mask = np.tril(np.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., nope:])
    return proj("o_proj", o.reshape(b, s, h * vd))


def _swiglu(p, x):
    import jax

    return (jax.nn.silu(x @ p["w1"].astype(x.dtype))
            * (x @ p["w3"].astype(x.dtype))) @ p["w2"].astype(x.dtype)


def route(cfg, h, router, bias):
    """(chosen experts (T, k), their weights (T, k)) of tokens ``h``."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(h @ router.astype(h.dtype))
    _, sel = jax.lax.top_k(s + bias.astype(s.dtype),
                           cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, sel, axis=-1)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return sel, cfg["routed_scaling_factor"] * w


def routed_experts(cfg, p, h, offset=None):
    """The held experts' part of an MoE layer for tokens ``h`` (T, D):
    routing over every expert, each held expert's SwiGLU computed for
    every token and weighted by the token's combine weight for it (zero
    where the expert is not chosen)."""
    import jax
    import jax.numpy as jnp

    offset = cfg["held_expert_offset"] if offset is None else offset
    sel, w = route(cfg, h, p["router"], p["bias"])
    comb = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        sel, cfg["n_routed_experts"], dtype=w.dtype))
    comb = comb[:, offset:offset + p["w1"].shape[0]]
    a = (jax.nn.silu(jnp.einsum("td,edf->tef", h, p["w1"].astype(h.dtype)))
         * jnp.einsum("td,edf->tef", h, p["w3"].astype(h.dtype)))
    return jnp.einsum("tef,efd,te->td", a, p["w2"].astype(h.dtype), comb)


def apply(cfg, params, state, x, train, frozen=None):
    """Logits (..., seq_len, vocab_size) of token ids ``x``; ``params``
    the adapters, ``frozen`` the base."""
    import jax

    del train
    base = frozen
    eps = cfg["rms_norm_eps"]
    lead = x.shape[:-1]
    ids = x.reshape(-1, x.shape[-1])
    dt = base["embed"].dtype
    hs = base["embed"][ids]
    take = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    for name in ("layers_dense", "layers_moe"):
        stack, ads = base[name], params[name]
        for i in range(stack["ln1"]["scale"].shape[0]):
            p, ad = take(stack, i), take(ads, i)
            hs = hs + _attention(cfg, p["attn"], ad,
                                 _rms(hs, p["ln1"]["scale"], eps))
            h2 = _rms(hs, p["ln2"]["scale"], eps)
            if name == "layers_dense":
                hs = hs + _swiglu(p["mlp"], h2)
            else:
                t = h2.reshape(-1, h2.shape[-1])
                hs = (hs + routed_experts(cfg, p["moe"], t).reshape(
                    h2.shape) + _swiglu(p["moe"]["shared"], h2))
    hs = _rms(hs, base["ln_f"]["scale"], eps)
    logits = hs @ base["head"].astype(dt)
    return logits.reshape(lead + logits.shape[-2:]), state


# ---------------------------------------------------------------------------
# the work, from shapes
# ---------------------------------------------------------------------------


def _held_per_token(cfg) -> float:
    """Assignments to held experts per token and MoE layer at a uniform
    router: top_k x held / routed (6 x 8 / 64 = 0.75)."""
    return (cfg["num_experts_per_tok"] * cfg["n_experts_held"]
            / cfg["n_routed_experts"])


def _expert_flops_token(cfg) -> float:
    """One token's held-expert products (three of d x f per assignment)
    over every MoE layer, forward, at the mean load."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return _n_moe(cfg) * _held_per_token(cfg) * 3 * 2 * d * f


def _parts(cfg) -> dict:
    """Forward FLOPs of one sequence by part: ``base`` (frozen products
    with one activation operand), ``attn`` (the score and value products,
    causal: the lower triangle with its diagonal), ``adapters``."""
    s, d = cfg["seq_len"], cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    n_layers = cfg["num_hidden_layers"]
    n_m = _n_moe(cfg)
    proj = sum(2 * a * b for a, b in _proj_shapes(cfg).values())
    lora = sum(2 * cfg["lora_rank"] * (a + b)
               for name, (a, b) in _proj_shapes(cfg).items()
               if name in cfg["lora_targets"])
    dense = cfg["first_k_dense_replace"] * 3 * 2 * d * cfg[
        "intermediate_size"]
    shared = n_m * 3 * 2 * d * cfg["moe_intermediate_size"] * cfg[
        "n_shared_experts"]
    router = n_m * 2 * d * cfg["n_routed_experts"]
    head = 2 * d * cfg["vocab_size"]
    per_token = (n_layers * proj + dense + shared + router + head
                 + _expert_flops_token(cfg))
    pairs = s * (s + 1) // 2
    attn = n_layers * 2 * h * (nope + rope + vd) * pairs
    return dict(base=s * per_token, attn=attn, adapters=s * n_layers * lora)


def flops_forward(cfg):
    """Model FLOPs of one sequence's forward pass (the held experts at
    the mean load of a uniform router; causal attention as its lower
    triangle)."""
    return float(sum(_parts(cfg).values()))


def flops_train(cfg):
    """One sequence's training step: the frozen products at two passes
    (forward and input gradient), the attention products at three (both
    of their operands are activations), the adapters at three (forward,
    input and weight gradients).  Recomputation does not count."""
    p = _parts(cfg)
    return float(2 * p["base"] + 3 * p["attn"] + 3 * p["adapters"])


def expert_flops_round(cfg, traffic) -> float:
    """The held experts' grouped products in one aggregation round at the
    mean load (``tokens x 6 x 8/64`` assignments a layer): forward and
    input gradient for every training token, forward for every
    evaluation token."""
    eng, pop = traffic["engine"], traffic["population"]
    train = (int(eng["k"]) * int(pop["samples_per_client"])
             * int(eng["local_epochs"]) * cfg["seq_len"])
    evals = int(traffic["eval_samples"]) * cfg["seq_len"]
    return float(_expert_flops_token(cfg) * (2 * train + evals))
