"""Config registry: 10 assigned architectures + the paper's own FL models.

``get_config(arch_id)`` returns the full-fidelity :class:`ModelConfig`;
``reduced_config(cfg)`` returns the CPU-smoke variant (<=2-ish layers,
d_model<=512, <=4 experts) of the same family, per the assignment contract.
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import FLConfig, INPUT_SHAPES, InputShape, ModelConfig
from repro.configs import (
    starcoder2_3b, qwen3_1_7b, zamba2_2_7b, kimi_k2_1t_a32b, xlstm_125m,
    internlm2_20b, minitron_4b, seamless_m4t_medium, granite_moe_1b_a400m,
    internvl2_76b,
)

ARCHS = {
    "starcoder2-3b": starcoder2_3b.CONFIG,
    "qwen3-1.7b": qwen3_1_7b.CONFIG,
    "zamba2-2.7b": zamba2_2_7b.CONFIG,
    "kimi-k2-1t-a32b": kimi_k2_1t_a32b.CONFIG,
    "xlstm-125m": xlstm_125m.CONFIG,
    "internlm2-20b": internlm2_20b.CONFIG,
    "minitron-4b": minitron_4b.CONFIG,
    "seamless-m4t-medium": seamless_m4t_medium.CONFIG,
    "granite-moe-1b-a400m": granite_moe_1b_a400m.CONFIG,
    "internvl2-76b": internvl2_76b.CONFIG,
}


def get_config(arch_id: str) -> ModelConfig:
    cfg = ARCHS[arch_id]
    cfg.validate()
    return cfg


def reduced_config(cfg: ModelConfig) -> ModelConfig:
    """Smoke-test variant of the same family: <=4 layers, d_model<=512,
    <=4 experts — runs a forward/train step on CPU in seconds."""
    kw = dict(
        d_model=256, n_heads=4, n_kv_heads=2, head_dim=0,
        vocab_size=512, vocab_pad_to=128, param_dtype="float32",
        compute_dtype="float32", remat=False, attn_chunk=0,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window
        else None,
        long_context_window=64, sharding="megatron",
    )
    if cfg.family in ("dense", "vlm"):
        kw.update(n_layers=2, d_ff=512,
                  n_prefix_tokens=8 if cfg.family == "vlm" else 0)
    elif cfg.family == "moe":
        kw.update(n_layers=2, d_ff=128, n_experts=4, top_k=2,
                  d_ff_dense=256 if cfg.d_ff_dense else 0,
                  moe_group_size=64,
                  first_k_dense=1 if cfg.first_k_dense else 0,
                  n_shared_experts=min(cfg.n_shared_experts, 1))
    elif cfg.family == "hybrid":
        kw.update(n_layers=4, hybrid_attn_every=2, d_ff=512,
                  ssm_state=16, ssm_head_dim=32, ssm_chunk=16)
    elif cfg.family == "ssm":
        kw.update(n_layers=2, d_ff=0)
    elif cfg.family == "audio":
        kw.update(n_layers=2, enc_layers=2, d_ff=512)
    out = dataclasses.replace(cfg, **kw)
    out.validate()
    return out
