"""The training step's attention on the TPU: JAX's fused Pallas flash
kernels (``jax.experimental.pallas.ops.tpu.flash_attention``), forward
and a custom VJP of dK/dV and dQ kernels, which skip the key blocks
above the causal diagonal.  Latent attention (MLA), whose values are
narrower than its queries and keys (d_v 128 against d_qk 192), takes
JAX's splash-attention kernels instead
(``jax.experimental.pallas.ops.tpu.splash_attention``), which the flash
kernels refuse it: forward, dQ and dK/dV, causal blocks skipped.

``models.layers.attention_block`` decides when to call it.  This module
imports nothing of the model, so the model can import it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as jax_flash
from jax.experimental.pallas.ops.tpu.splash_attention import \
    splash_attention_kernel as splash
from jax.experimental.pallas.ops.tpu.splash_attention import \
    splash_attention_mask as splash_mask


def block_sizes(seq: int):
    """The kernels' blocks for a sequence of ``seq``: the largest of
    512/256/128 rows that divides it, for every block of the forward,
    dK/dV and dQ kernels; None where none does."""
    for b in (512, 256, 128):
        if seq % b == 0:
            return jax_flash.BlockSizes(
                block_q=b, block_k_major=b, block_k=b, block_b=1,
                block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b,
                block_q_dkv=b, block_k_major_dq=b, block_k_dq=b,
                block_q_dq=b)
    return None


def train_attention(q, k, v, *, causal: bool = True, window=None):
    """Attention of a training step on one TPU.  q, k, v go to the MXU
    in their own dtype with f32 accumulation; the softmax statistics
    stay f32.  q (B, Hq, S, D), k/v (B, Hkv, S, D), S a multiple of 128;
    grouped kv heads are repeated.  The kernels take no window."""
    if window is not None:
        raise ValueError("the fused attention kernels take no window")
    _, hq, s, d = q.shape
    n_rep = hq // k.shape[1]
    if n_rep > 1:
        k, v = jnp.repeat(k, n_rep, axis=1), jnp.repeat(v, n_rep, axis=1)
    return jax_flash.flash_attention(q, k, v, causal=causal,
                                     sm_scale=d ** -0.5,
                                     block_sizes=block_sizes(s))


def _splash_kernel(heads: int, seq: int, block: int):
    """The causal splash kernel for ``heads`` heads of ``seq`` rows, every
    block ``block`` rows.  Built anew at each trace: it holds its block
    masks as arrays of the trace it was made in."""
    mask = splash_mask.MultiHeadMask(
        [splash_mask.CausalMask((seq, seq)) for _ in range(heads)])
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    return splash.make_splash_mha_single_device(mask, block_sizes=sizes)


def latent_attention(q, k, v):
    """Causal attention of a training step on one TPU where the values
    are narrower than the queries and keys, as in MLA: q, k (B, H, S,
    D_qk), v (B, H, S, D_v), S a multiple of 128.  Scores are scaled by
    D_qk^-1/2, applied to q before the kernel, which takes no scale.
    Returns (B, H, S, D_v)."""
    _, h, s, d = q.shape
    block = block_sizes(s).block_q
    kernel = _splash_kernel(h, s, block)
    return jax.vmap(kernel)((q * d ** -0.5).astype(q.dtype), k, v)
