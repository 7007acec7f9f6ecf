"""Model FLOPs per token of a DeepSeek-V3-style decoder on one chip's
share of its experts: 6·N_active + 6·L·heads·(d_qk + d_v)·S, forward and
backward of every matmul parameter a token uses and of attention's two
matmuls, with no recomputation counted.

N_active: every layer's latent attention (W_q, W_kva, W_kvb, W_o); the
dense layers' SwiGLU; each expert layer's router, its shared experts,
and its held routed experts at the share of a token they see, top-k ×
held / published experts (6 × 8/64 = 0.75 of an expert); the LM head
once (the embedding's lookup is no matmul).  Attention's QKᵀ is d_qk =
nope + rope wide and PV d_v, over the whole S × S as the dense rule
counts it (``dense_decoder.py``).
"""
from __future__ import annotations


def attention_params(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    dv, r = c["v_head_dim"], c["kv_lora_rank"]
    return (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
            + h * dv * d)


def active_params(c: dict) -> float:
    d = c["hidden_size"]
    n, nd = c["num_hidden_layers"], c["first_k_dense_replace"]
    fe = c["moe_intermediate_size"]
    routed = (c["num_experts_per_tok"] * c["n_routed_experts"]
              / c["published"]["n_routed_experts"])
    expert_layer = (d * c["published"]["n_routed_experts"]
                    + 3 * d * fe * (c["n_shared_experts"] + routed))
    return (n * attention_params(c) + nd * 3 * d * c["intermediate_size"]
            + (n - nd) * expert_layer + d * c["vocab_size"])


def per_token(config: dict, traffic: dict, lane_info: dict) -> float:
    c = config
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (6.0 * active_params(c)
            + 6.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * (qk + c["v_head_dim"]) * traffic["seq"])
