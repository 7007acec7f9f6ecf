"""The work of a training step's latent attention (MLA): JAX's Pallas
splash-attention kernels (forward ``splash_mha_fwd_*``,
``splash_mha_dkv_*``, ``splash_mha_dq_*``) at d_qk = nope + rope and
d_v, under full remat, from the configuration and the traffic alone.

Operations: per layer, head and row of the batch, the forward twice
(QKᵀ over d_qk, PV over d_v; once more in the remat recompute), dK/dV
four matmuls (QKᵀ, dV = PᵀdO, dP = dO Vᵀ, dK = dSᵀQ: 2 d_qk + 2 d_v) and
dQ three (QKᵀ, dP, dQ = dS K: 2 d_qk + d_v), so 6 d_qk + 5 d_v; each
counts the causal half that the mathematics needs, S²·D of 2·S²·D.  The
diagonal blocks that the kernels run whole are not counted, so the
share cannot pass 100%.

Bytes: what each kernel must read and write at least, once: the forward
q, k (d_qk), v, o (d_v), twice; dK/dV q, k, dK and v, dO, dV; dQ q, k,
dQ and v, dO; and the rows' float32 logsumexp and di, read by both
backward kernels and written by the forward.
"""
from __future__ import annotations

# instruction names of the kernels' device ops
OPS = r"splash_mha_(fwd|dkv|dq)_.*"
ROWS = 2 + 2 + 2
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _sizes(c: dict, t: dict):
    return (c["num_hidden_layers"], t["batch"], t["seq"],
            c["num_attention_heads"],
            c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["v_head_dim"])


def flops(c: dict, t: dict) -> float:
    """Operations of a step."""
    layers, b, s, h, dqk, dv = _sizes(c, t)
    return float(layers * b * h * s * s * (6 * dqk + 5 * dv))


def bytes(c: dict, t: dict) -> float:
    """Bytes a step's kernels read and write at least."""
    layers, b, s, h, dqk, dv = _sizes(c, t)
    e = DTYPE_BYTES[c["torch_dtype"]]
    # forward twice: q, k, v, o; dK/dV: q, k, dK, v, dO, dV; dQ: q, k, dQ,
    # v, dO
    per_row = (2 * (2 * dqk + 2 * dv) + (3 * dqk + 3 * dv)
               + (3 * dqk + 2 * dv)) * e + ROWS * 4
    return float(layers * b * s * h * per_row)
