"""moonshot-v1-16b-a3b [moe]: Moonlight-16B-A3B (``deepseek_v3``), as
published in moonshotai/Moonlight-16B-A3B's config.json: 27 layers,
d_model 2048, 16 heads of multi-head latent attention (``kv_lora_rank``
512, no ``q_lora_rank``, nope/rope/v head sizes 128/64/128), one leading
dense layer of width 11264, then 64 routed experts of width 1408, 6 per
token, plus 2 shared experts; sigmoid router with a score-correction
bias (``noaux_tc``, one group), top-6 weights normalised and scaled by
2.446; rope theta 50000, RMSNorm eps 1e-5, vocabulary 163840, untied.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab_size=163840,
    rope_theta=50000.0,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  num_shared_experts=2, first_k_dense=1, dense_d_ff=11264,
                  scoring="sigmoid", norm_topk=True, routed_scaling=2.446,
                  sharding_mode="ep"),
    norm_eps=1e-5,
    max_seq_len=8192,
    train_microbatches=2,
)

SMOKE_CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    d_ff=48,
    vocab_size=256,
    rope_theta=50000.0,
    mla=MLAConfig(kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    # the published routing (64 experts, 6 a token) at small widths
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=32,
                  num_shared_experts=2, first_k_dense=1, dense_d_ff=192,
                  scoring="sigmoid", norm_topk=True, routed_scaling=2.446,
                  sharding_mode="ep"),
    norm_eps=1e-5,
    max_seq_len=256,
)
