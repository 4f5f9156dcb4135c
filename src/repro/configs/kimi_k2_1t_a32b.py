"""Kimi K2 1T-A32B [https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json]
— trillion-parameter MoE (``model_type`` deepseek_v3).

61 layers (first dense, width 18,432), 384 experts top-8 + 1 shared
expert, d_ff=2048 per expert.  bf16 params + plain SGD (the paper's
client optimizer) + fully-sharded ("fsdp") policy so params+grads fit
one v5e pod (see DESIGN.md §5).

Departures from the published block: attention is GQA (64 heads, 8 KV
heads) where the model uses multi-head latent attention (``q_lora_rank``
1,536, ``kv_lora_rank`` 512; :mod:`repro.models.mla` has no KV cache for
the serving paths this config's tests drive), and the router is the
softmax/capacity one where the model routes by biased sigmoid scores.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b", family="moe",
    n_layers=61, d_model=7168, n_heads=64, n_kv_heads=8,
    d_ff=2048, d_ff_dense=18432, vocab_size=163840,
    n_experts=384, top_k=8, n_shared_experts=1, first_k_dense=1,
    capacity_factor=1.25, moe_group_size=512,
    attn_chunk=2048, param_dtype="bfloat16", optimizer="sgd",
    sharding="fsdp",
    source="https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/"
           "config.json",
)
