"""deepseek-v2-lite [moe] — latent attention, 64 routed + 2 shared experts
top-6, first layer dense, YaRN rope. [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite config.json]

Served from the paged layout only: its cache is one latent pool.  Published
as bfloat16 weights, held so here."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    num_layers=27, d_model=2048, vocab_size=102400,
    num_heads=16, num_kv_heads=16, head_dim=192,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    yarn_factor=40.0, yarn_original_max_pos=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
    rope_theta=10000.0,
    d_ff=1408, num_experts=64, top_k=6, num_shared_experts=2,
    norm_topk_prob=False, first_dense_layers=1, dense_d_ff=10944,
    norm_type="rmsnorm", norm_eps=1e-6, mlp_act="silu",
    tie_embeddings=False, param_dtype="bfloat16",
)


def smoke_config() -> ModelConfig:
    """1 dense and 2 expert layers, 8 experts with 2 a token, 1 shared; in
    float32, so that the CPU tests hold it to the float32 reference by
    rounding alone (in bf16 a near tie in the router flips an expert)."""
    return CONFIG.replace(num_layers=3, d_model=64, vocab_size=288,
                          param_dtype="float32", compute_dtype="float32",
                          num_heads=4, num_kv_heads=4, head_dim=24,
                          kv_lora_rank=32, qk_nope_head_dim=16,
                          qk_rope_head_dim=8, v_head_dim=16,
                          d_ff=32, num_experts=8, top_k=2,
                          num_shared_experts=1, dense_d_ff=96)
