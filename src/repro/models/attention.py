"""Attention implementations.

``chunked_attention`` is the default: an online-softmax attention that
scans over KV blocks, so peak memory is O(seq * block) instead of
O(seq^2) — required for the 32k prefill dry-runs on the production mesh
and it doubles as the pure-jnp oracle for the Pallas flash kernel
(kernels/flash_attention.py).

Layouts: q (B, Hq, Sq, D), k/v (B, Hkv, Skv, D); GQA repeats kv heads.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return x
    b, h, s, d = x.shape
    return jnp.broadcast_to(x[:, :, None], (b, h, n_rep, s, d)).reshape(
        b, h * n_rep, s, d)


def naive_attention(q, k, v, *, causal: bool = True,
                    q_offset: int = 0, sm_scale: Optional[float] = None,
                    window: Optional[int] = None) -> jax.Array:
    """O(Sq*Skv) reference — only for tiny test shapes."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    k = repeat_kv(k, hq // hkv)
    v = repeat_kv(v, hq // hkv)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    skv = k.shape[2]
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def chunked_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                      sm_scale: Optional[float] = None,
                      window: Optional[int] = None,
                      block_kv: int = 512) -> jax.Array:
    """Online-softmax attention, scanning KV in blocks of ``block_kv``.

    Equivalent to naive_attention for any shapes (same math, different
    association order), with O(Skv/block) sequential steps and no
    materialized (Sq, Skv) score matrix.
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    scale = sm_scale if sm_scale is not None else d ** -0.5
    nb = -(-skv // block_kv)
    pad = nb * block_kv - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, hkv, nb, block_kv, d)
    vb = v.reshape(b, hkv, nb, block_kv, d)
    qpos = jnp.arange(sq) + q_offset
    q32 = (q * scale).astype(jnp.float32)

    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, kpos = blk                  # (b,hkv,bk,d) ×2, (bk,)
        kblk = repeat_kv(kblk, n_rep)
        vblk = repeat_kv(vblk, n_rep)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       kblk.astype(jnp.float32))
        mask = kpos[None, :] <= (qpos[:, None] if causal
                                 else jnp.full((sq, 1), skv + q_offset))
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        mask &= (kpos < skv)[None, :]           # padding
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, hq, sq), dtype=jnp.float32)
    a0 = jnp.zeros((b, hq, sq, d), dtype=jnp.float32)
    kpos_all = jnp.arange(nb * block_kv).reshape(nb, block_kv)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), kpos_all))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# flash attention (custom VJP): linear-memory forward AND backward.
# Values may be narrower than queries and keys (MLA); scores are scaled
# by the query's width.
# The plain chunked_attention above is mathematically identical but its
# scan saves per-block probabilities for autodiff — O(Sq*Skv) residuals.
# This version saves only (out, logsumexp) and recomputes scores per
# block in the backward, exactly like FlashAttention-2; it is the
# pure-jnp oracle for kernels/flash_attention.py.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, q_offset: int = 0,
                    window: Optional[int] = None, block_kv: int = 512,
                    unroll: bool = False):
    out, _ = _flash_fwd_impl(q, k, v, causal, q_offset, window, block_kv,
                             unroll)
    return out


def _flash_fwd_impl(q, k, v, causal, q_offset, window, block_kv,
                    unroll: bool = False):
    b, hq, sq, d = q.shape
    hkv, skv, dv = k.shape[1], k.shape[2], v.shape[-1]
    n_rep = hq // hkv
    scale = d ** -0.5
    nb = -(-skv // block_kv)
    pad = nb * block_kv - skv
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else v
    kb = jnp.moveaxis(kp.reshape(b, hkv, nb, block_kv, d), 2, 0)
    vb = jnp.moveaxis(vp.reshape(b, hkv, nb, block_kv, dv), 2, 0)
    qpos = jnp.arange(sq) + q_offset
    q32 = (q * scale).astype(jnp.float32)
    kpos_all = jnp.arange(nb * block_kv).reshape(nb, block_kv)

    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, kpos = blk
        kblk = repeat_kv(kblk, n_rep).astype(jnp.float32)
        vblk = repeat_kv(vblk, n_rep).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kblk)
        mask = _blk_mask(kpos, qpos, causal, window, skv, q_offset)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, hq, sq), dtype=jnp.float32)
    a0 = jnp.zeros((b, hq, sq, dv), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, kpos_all),
                                  unroll=unroll)
    out = (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))       # logsumexp rows
    return out, lse


def _blk_mask(kpos, qpos, causal, window, skv, q_offset):
    sq = qpos.shape[0]
    if causal:
        mask = kpos[None, :] <= qpos[:, None]
    else:
        mask = jnp.ones((sq, kpos.shape[0]), dtype=bool)
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    mask &= (kpos < skv)[None, :]
    return mask


def _flash_fwd(q, k, v, causal, q_offset, window, block_kv,
               unroll=False):
    out, lse = _flash_fwd_impl(q, k, v, causal, q_offset, window,
                               block_kv, unroll)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_offset, window, block_kv, unroll, res, dout):
    q, k, v, out, lse = res
    b, hq, sq, d = q.shape
    hkv, skv, dv = k.shape[1], k.shape[2], v.shape[-1]
    n_rep = hq // hkv
    scale = d ** -0.5
    nb = -(-skv // block_kv)
    pad = nb * block_kv - skv
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else v
    kb = jnp.moveaxis(kp.reshape(b, hkv, nb, block_kv, d), 2, 0)
    vb = jnp.moveaxis(vp.reshape(b, hkv, nb, block_kv, dv), 2, 0)
    kpos_all = jnp.arange(nb * block_kv).reshape(nb, block_kv)
    qpos = jnp.arange(sq) + q_offset
    q32 = (q * scale).astype(jnp.float32)
    do32 = dout.astype(jnp.float32)
    # D_i = rowsum(dout * out)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)  # (b,hq,sq)

    def step(dq_acc, blk):
        kblk, vblk, kpos = blk
        kr = repeat_kv(kblk, n_rep).astype(jnp.float32)
        vr = repeat_kv(vblk, n_rep).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kr)
        mask = _blk_mask(kpos, qpos, causal, window, skv, q_offset)
        s = jnp.where(mask[None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                   # (b,hq,sq,bk)
        dv_r = jnp.einsum("bhqk,bhqd->bhkd", p, do32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do32, vr)
        ds = p * (dp - delta[..., None])                  # (b,hq,sq,bk)
        dq_blk = jnp.einsum("bhqk,bhkd->bhqd", ds, kr) * scale
        dk_r = jnp.einsum("bhqk,bhqd->bhkd", ds, q32)
        # fold grouped heads back to kv heads
        dk_g = dk_r.reshape(b, hkv, n_rep, block_kv, d).sum(axis=2)
        dv_g = dv_r.reshape(b, hkv, n_rep, block_kv, dv).sum(axis=2)
        return dq_acc + dq_blk, (dk_g, dv_g)

    dq0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, (kb, vb, kpos_all),
                                    unroll=unroll)
    dk = jnp.moveaxis(dk_b, 0, 2).reshape(b, hkv, nb * block_kv, d)
    dvv = jnp.moveaxis(dv_b, 0, 2).reshape(b, hkv, nb * block_kv, dv)
    if pad:
        dk, dvv = dk[:, :, :skv], dvv[:, :, :skv]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dvv.astype(v.dtype))


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_ref(q, k, v, *, causal=True, q_offset=0,
                        sm_scale=None, window=None, block_kv=512,
                        unroll=False):
    """Signature-compatible wrapper used as the default attention impl."""
    return flash_attention(q, k, v, causal, q_offset, window, block_kv,
                           unroll)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     sm_scale: Optional[float] = None,
                     window: Optional[int] = None) -> jax.Array:
    """Single-token decode: q (B, Hq, 1, D) against a (B, Hkv, S, D)
    cache with ``cache_len`` valid positions."""
    b, hq, _, d = q.shape
    hkv, smax = k_cache.shape[1], k_cache.shape[2]
    k = repeat_kv(k_cache, hq // hkv)
    v = repeat_kv(v_cache, hq // hkv)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", (q * scale).astype(jnp.float32),
                   k.astype(jnp.float32))
    kpos = jnp.arange(smax)[None, None, None, :]
    mask = kpos < cache_len
    if window is not None:
        mask &= kpos >= cache_len - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
