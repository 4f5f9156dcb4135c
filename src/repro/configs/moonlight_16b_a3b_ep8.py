"""Moonlight-16B-A3B [https://huggingface.co/moonshotai/Moonlight-16B-A3B]
(``model_type`` deepseek_v3), cut to one chip's share of an 8-chip
expert-parallel layer, for federated LoRA fine-tuning.

Published widths, all kept: hidden 2,048; 16 heads of multi-head latent
attention (no query latent, ``kv_lora_rank`` 512, ``qk_nope_head_dim``
128, ``qk_rope_head_dim`` 64, ``v_head_dim`` 128); 64 routed experts of
width 1,408, 6 a token, sigmoid scores with a selection-only bias
(``noaux_tc``, one group), chosen weights normalised and scaled by
2.446; 2 shared experts of 1,408; a dense first layer of 11,264; RMSNorm
eps 1e-5, rope theta 50,000, untied head, no biases.

The cut (``model-configs`` section 4): 8 chips share each layer, by
expert parallelism (8 of the 64 experts a chip) and a vocabulary slice
(20,480 of the 163,840 ids a chip); this is chip 0's share (experts 0-7,
ids 0-20,479), and the layers left out (27 -> the dense layer plus 4 MoE
layers) lie on further pipeline stages.  Every layer routes over all 64
experts and computes the 8 it holds.

Clients train rank-16 LoRA adapters (alpha 32; A ~ N(0, 1/in), B = 0)
on ``q_proj``, ``kv_a_proj_with_mqa``, ``kv_b_proj`` and ``o_proj`` of
every layer over the frozen base (FedIT, arXiv:2305.05644), through
:func:`repro.models.transformer.lora_apply`.

Departures: rope rotates split halves (the published interleaved layout
is a permutation of the rope columns); no auxiliary balance loss and no
bias update (the router is frozen); f32 weights and activations.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b-ep8", family="moe",
    n_layers=5, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, d_ff_dense=11264, vocab_size=20480,
    attn_type="mla", kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_theta=50_000.0,
    n_experts=64, top_k=6, n_shared_experts=2, first_k_dense=1,
    router="sigmoid", routed_scaling_factor=2.446,
    n_experts_held=8, held_expert_offset=0,
    norm_eps=1e-5, param_dtype="float32", compute_dtype="float32",
    tie_embeddings=False, remat=True,
    lora_rank=16, lora_alpha=32.0,
    lora_targets=("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj"),
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
           "config.json",
)
