"""moonlight-16b-a3b [moe] — 27L d_model=2048 16H, MLA without a query
latent (kv_lora 512, nope 128 / rope 64 per head, v_head_dim 128),
layer 0 a dense FFN of width 11264, layers 1..26 MoE: 64 routed experts
of width 1408, top-6, sigmoid scores with a selection bias (noaux_tc,
one group), the chosen scores renormalised and times 2.446, plus 2
shared experts (2 x 1408). rope_theta 50000, untied embeddings over
163,840 tokens. [huggingface.co/moonshotai/Moonlight-16B-A3B, config.json]

DeepSeek-V3's block: the rope dims rotate in adjacent pairs.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab_size=163840,
    head_dim=128,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    moe=MoEConfig(num_experts=64, top_k=6, expert_ff=1408,
                  num_shared=2, shared_ff=2816, scoring="sigmoid",
                  score_bias=True, norm_topk=True, routed_scale=2.446),
    first_dense_ff=11264,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True),
    param_dtype="bfloat16",
)
