"""Model FLOPs per token of a dense decoder: 6·N + 12·L·(heads ·
head_dim)·S, forward and backward of every parameter and of attention's
two matmuls, with no recomputation counted.  N counts a tied embedding
once, so it is the LM head's matmul; the lookup is no matmul."""
from __future__ import annotations


def params(c: dict) -> int:
    d, ff = c["hidden_size"], c["intermediate_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim", d // hq)
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    if c.get("qkv_bias"):
        attn += (hq + 2 * hkv) * hd
    layer = attn + 3 * d * ff + 2 * d
    emb = c["vocab_size"] * d * (1 if c["tie_word_embeddings"] else 2)
    return c["num_hidden_layers"] * layer + emb + d


def per_token(config: dict, traffic: dict, lane_info: dict) -> float:
    hq = config["num_attention_heads"]
    hd = config.get("head_dim", config["hidden_size"] // hq)
    return (6.0 * params(config)
            + 12.0 * config["num_hidden_layers"] * hq * hd * traffic["seq"])
