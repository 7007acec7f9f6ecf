"""FlashAttention-2 forward Pallas kernel (TPU target, validated with
interpret=True on CPU).

Canonical TPU structure: grid (batch*q_heads, q_blocks, kv_blocks) with
the KV dimension innermost — TPU grids execute sequentially over the
last axis, so the online-softmax state (m, l, acc) lives in VMEM scratch
and carries across kv steps; the output tile is written on the last kv
step.  Q/K/V tiles are MXU-aligned (block sizes multiples of 128 at
production shapes; tests sweep smaller blocks in interpret mode).

The backward pass reuses the pure-jnp flash backward from
``repro.models.attention`` (same math as the FA2 paper); a dedicated
backward kernel is a further optimization the wrapper can swap in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                      *, sm_scale: float, causal: bool, block_q: int,
                      block_kv: int, n_kv: int, skv: int, q_offset: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * sm_scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = q @ k.T                                          # (bq, bk)

    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + q_offset
    kpos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = kpos < skv
    if causal:
        mask &= kpos <= qpos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + p @ v
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_fwd_pallas(q, k, v, *, causal: bool = True,
                               q_offset: int = 0,
                               block_q: int = 128, block_kv: int = 128,
                               interpret: bool = True) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D) with Hq % Hkv == 0.
    GQA is handled by flattening (B, Hq) and indexing kv heads."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    # up to block_q query rows are one whole block; more are tiled by
    # block_q (a multiple of 8) with zero-padded rows, sliced off below
    bq = min(block_q, sq)
    sq_pad = -(-sq // bq) * bq
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0)))
    nk = -(-skv // block_kv)
    pad = nk * block_kv - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qf = q.reshape(b * hq, sq_pad, d)
    kf = k.reshape(b * hkv, nk * block_kv, d)
    vf = v.reshape(b * hkv, nk * block_kv, d)

    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=d ** -0.5, causal=causal,
        block_q=bq, block_kv=block_kv, n_kv=nk, skv=skv,
        q_offset=q_offset)
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sq_pad // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_kv, d),
                         lambda h, i, j, n_rep=n_rep: (h // n_rep, j, 0)),
            pl.BlockSpec((1, block_kv, d),
                         lambda h, i, j, n_rep=n_rep: (h // n_rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max m
            pltpu.VMEM((bq,), jnp.float32),       # running denom l
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq_pad, d)[:, :, :sq]
