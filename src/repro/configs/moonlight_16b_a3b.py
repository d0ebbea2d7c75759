"""moonlight-16b-a3b — deepseek-v3 layout: MLA attention, one leading dense
layer, then 26 MoE layers of 64 routed experts (top-6, sigmoid scores with a
selection bias, ``noaux_tc`` with one group) beside 2 shared experts.

``moonlight-16b-a3b-ep4`` is what one chip of a four-way expert-parallel
deployment holds: experts 0-15 of each MoE layer, and everything else
(attention, router, shared experts, dense layer, vocabulary) whole. It routes
over all 64 and adds only its own experts' share; nothing stands in for the
other three chips.

[hf:moonshotai/Moonlight-16B-A3B config.json; hf]
"""
from repro.configs.base import ModelConfig, MOE

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family=MOE,
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,                  # moe_intermediate_size
    vocab_size=163840,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    router_score="sigmoid",     # noaux_tc: bias chooses, renormalized
    routed_scaling=2.446,
    first_k_dense=1,
    d_ff_dense=11264,           # intermediate_size
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=5e4,
    norm_eps=1e-5,
    max_seq_len=8192,
    tie_embeddings=False,
    moe_impl="ragged",          # the one path that routes the deepseek-v3 way
    grad_accum=2,
    source="[hf:moonshotai/Moonlight-16B-A3B; hf]",
)

EP4 = CONFIG.replace(name="moonlight-16b-a3b-ep4", held_experts=16,
                     first_held_expert=0)
