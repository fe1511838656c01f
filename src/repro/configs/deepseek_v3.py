"""DeepSeek-V3 [hf:deepseek-ai/DeepSeek-V3 config.json; arXiv:2412.19437]
— MLA attention, 3 dense layers then 58 MoE layers of 256 routed
experts (top-8 from at most 4 of 8 groups) and 1 shared, one MTP
module.

The traffic plane lowers it (``apps.collectives_lowering``); the
adapted model layer (``repro.models``) has no MLA, leading dense
layers or shared experts, and refuses it (``models.model.supports``).
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek_v3", family="moe",
    n_layers=61,                    # num_hidden_layers
    d_model=7168,                   # hidden_size
    n_heads=128,                    # num_attention_heads
    n_kv_heads=128,                 # num_key_value_heads
    vocab_size=129280,
    rope_theta=1e4,
    norm_eps=1e-6,                  # rms_norm_eps
    n_dense_layers=3,               # first_k_dense_replace
    dense_d_ff=18432,               # intermediate_size
    pattern=(("attn", "moe"),),     # moe_layer_freq 1
    n_experts=256,                  # n_routed_experts
    top_k=8,                        # num_experts_per_tok
    moe_d_ff=2048,                  # moe_intermediate_size
    n_shared_experts=1,
    router_bias=True,               # topk_method noaux_tc
    n_group=8,
    topk_group=4,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_mtp_layers=1,                 # num_nextn_predict_layers
)

SMOKE = CONFIG.replace(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, vocab_size=256,
    n_dense_layers=1, dense_d_ff=128,
    n_experts=16, top_k=4, moe_d_ff=32, n_group=4, topk_group=2,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
)
