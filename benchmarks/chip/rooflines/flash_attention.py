"""The work of a training step's fused causal attention: JAX's Pallas
flash kernels (``flash_attention`` forward, ``flash_mha_bwd_dkv``,
``flash_mha_bwd_dq``) under full remat, from the configuration and the
traffic alone.

Operations: each layer runs 11 matmuls over S x S scores of a head, the
forward twice (QKᵀ, PV; once more in the remat recompute), dK/dV four
(QKᵀ, dV = PᵀdO, dP = dO Vᵀ, dK = dSᵀQ) and dQ three (QKᵀ, dP, dQ =
dS K).  Each counts the causal half that the mathematics needs, 2·B·H·
S²·D / 2 = B·H·S²·D; the diagonal blocks that the kernels run whole are
not counted, so the share cannot pass 100%.

Bytes: what each kernel must read and write at least, once: the forward
q, k, v and o; dK/dV q, k, v, dO, dK, dV and the rows' l, m and di
(float32); dQ q, k, v, dO, dQ and l, m, di.  q, k, v take the
configuration's dtype.
"""
from __future__ import annotations

# instruction names of the kernels' device ops
OPS = r"flash_attention(\.\d+)?|flash_mha_bwd_dkv_.*|flash_mha_bwd_dq_.*"
MATMULS = 11
# (B, S, H, D) tensors and (B, S, H) float32 rows a layer moves
TENSORS = 2 * 4 + 6 + 5
ROWS = 3 + 3
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _sizes(c: dict, t: dict):
    return (c["num_hidden_layers"], t["batch"], t["seq"],
            c["num_attention_heads"], c["head_dim"])


def flops(c: dict, t: dict) -> float:
    """Operations of a step."""
    layers, b, s, h, d = _sizes(c, t)
    return float(MATMULS * layers * b * h * s * s * d)


def bytes(c: dict, t: dict) -> float:
    """Bytes a step's kernels read and write at least."""
    layers, b, s, h, d = _sizes(c, t)
    e = DTYPE_BYTES[c["torch_dtype"]]
    return float(layers * b * s * h * (TENSORS * d * e + ROWS * 4))
