"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B, model_type
deepseek_v3] — 27L d_model=2048 16H; multi-head latent attention
(kv_lora_rank 512, no query compression, q/k heads of 128 nope + 64
rope dims, values of 128); layer 0 a dense SwiGLU of 11264, layers 1-26
MoE: 64 routed experts of 1408, top-6 by sigmoid scores plus a
correction bias (noaux_tc, one group), weights normalised and scaled by
2.446, and 2 shared experts; untied head, vocab 163840, RMSNorm eps
1e-5, rope theta 50000, context 8192."""
from repro.models import ArchConfig, MoECfg

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab=163840,
    rms_norm_eps=1e-5, rope_theta=50000.0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, n_dense_layers=1,
    qkv_bias=False, tie_embeddings=False,
    act="swiglu", norm="rmsnorm", rope=True,
    moe=MoECfg(n_experts=64, top_k=6, n_shared=2, d_expert=1408,
               router="sigmoid", routed_scaling=2.446),
    source="hf:moonshotai/Moonlight-16B-A3B",
)
