"""Transformer building blocks, pure-functional JAX.

Everything takes explicit param pytrees (dicts of arrays) so layers stack
cleanly under ``lax.scan`` and shard cleanly under pjit.  Perf-critical
ops (rmsnorm, attention, expert matmul, ssm scan) route through an
``impl`` registry so the Pallas kernels can be swapped in on TPU while
the chunked-jnp references run everywhere (DESIGN.md §6).  Attention
without a KV cache takes JAX's fused Pallas flash kernels by itself
where it can (``attention_path``): on one TPU, unwindowed, at lengths
the kernels tile.
"""
from __future__ import annotations

import collections
import functools
import threading
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.train_attention import block_sizes, latent_attention, \
    train_attention
from ..kernels.train_gmm import grouped_matmul, tiling as gmm_tiling
from .attention import decode_attention

# ---------------------------------------------------------------------------
# impl registry (kernels plug in here)
# ---------------------------------------------------------------------------

_IMPLS: dict[str, Callable] = {}


def register_impl(name: str, fn: Callable) -> None:
    _IMPLS[name] = fn


def get_impl(name: str, default: Callable) -> Callable:
    return _IMPLS.get(name, default)


# ---------------------------------------------------------------------------
# logical-axis sharding constraints (set by the launch layer; no-op when
# no mapping is active, e.g. CPU smoke tests)
# ---------------------------------------------------------------------------

_AXIS_MAP: dict[str, Any] = {}


def set_axis_map(mapping: Optional[dict]) -> None:
    """mapping: logical -> mesh axis (or tuple), e.g.
    {"dp": ("pod", "data"), "tp": "model"}."""
    global _AXIS_MAP
    _AXIS_MAP = dict(mapping or {})


def constrain(x, *logical):
    """with_sharding_constraint on logical axes ('dp'/'tp'/None) under
    the mesh of ``set_axis_map``.  A dim that the axis size does not
    divide stays unconstrained; any other failure raises."""
    if not _AXIS_MAP:
        return x
    from jax.sharding import PartitionSpec as P
    mesh = _AXIS_MAP.get("mesh")
    axes = []
    for dim, a in zip(x.shape, logical):
        ax = _AXIS_MAP.get(a) if a else None
        if ax is not None and mesh is not None:
            names = (ax,) if isinstance(ax, str) else tuple(ax)
            if dim % int(np.prod([mesh.shape[n] for n in names])):
                ax = None
        axes.append(ax)
    return jax.lax.with_sharding_constraint(x, P(*axes))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_ref(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rmsnorm(x, w, eps: float = 1e-6):
    return get_impl("rmsnorm", rmsnorm_ref)(x, w, eps)


def layernorm(x, w, b, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 1e4) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 1e4) -> jax.Array:
    """x: (B, H, S, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, sections=(16, 24, 24),
                theta: float = 1e4) -> jax.Array:
    """Qwen2-VL multimodal RoPE: rotary dims partitioned into (temporal,
    height, width) sections, each rotated by its own position stream.
    x: (B, H, S, D); positions: (3, B, S)."""
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(d, theta)                       # (half,)
    # section index for each rotary dim
    sec_pos = []
    for si, sec in enumerate(sections):
        sec_pos.extend([si] * sec)
    sec_idx = jnp.array(sec_pos)                       # (half,)
    pos = positions.astype(jnp.float32)                # (3, B, S)
    # choose, per rotary dim, the position stream of its section
    p = pos[sec_idx]                                   # (half, B, S)
    ang = jnp.moveaxis(p, 0, -1) * freqs               # (B, S, half)
    cos = jnp.cos(ang)[:, None]                        # (B,1,S,half)
    sin = jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def default_mrope_positions(batch: int, seq: int) -> jax.Array:
    """Text-only M-RoPE positions: all three streams equal."""
    p = jnp.broadcast_to(jnp.arange(seq)[None], (batch, seq))
    return jnp.stack([p, p, p])


# ---------------------------------------------------------------------------
# attention block (GQA, optional qkv bias / M-RoPE / window)
# ---------------------------------------------------------------------------

_ATTN_PATHS: collections.Counter = collections.Counter()
_ATTN_PATHS_LOCK = threading.Lock()


def _devices_spanned() -> int:
    """Over how many devices the step being traced may run: the size of
    the axis map's mesh, else of the context's mesh, else every device
    (a jit given no mesh may still be given inputs sharded over them)."""
    mesh = _AXIS_MAP.get("mesh")
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return jax.device_count()
    return mesh.size


def attention_path(seq: int, *, kv_cache: bool, window,
                   latent: bool = False) -> str:
    """The path an attention call of ``attention_block`` at length
    ``seq`` takes: "fused" where ``train_attention`` (``latent_attention``
    for MLA) can run it, else the first reason it cannot:

      platform    the default backend is not a TPU
      kv_cache    a decode step against a KV cache
      registered  an impl is registered for "attention"
      window      sliding-window attention
      shape       a length that is not a multiple of 128
      sharded     the step may run over more than one device: the TPU
                  compiler does not partition a Pallas kernel

    Tallied at trace time under the answer (``attention_paths``), an
    MLA call's as "mla_<answer>"."""
    if jax.default_backend() != "tpu":
        path = "platform"
    elif kv_cache:
        path = "kv_cache"
    elif "attention" in _IMPLS:
        path = "registered"
    elif window is not None:
        path = "window"
    elif block_sizes(seq) is None:
        path = "shape"
    elif _devices_spanned() > 1:
        path = "sharded"
    else:
        path = "fused"
    with _ATTN_PATHS_LOCK:
        _ATTN_PATHS["mla_" + path if latent else path] += 1
    return path


def attention_paths() -> dict:
    """Attention calls traced in this process so far, by path."""
    with _ATTN_PATHS_LOCK:
        return dict(_ATTN_PATHS)


def init_attn(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
              qkv_bias: bool, dtype) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d_model ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (d_model, n_heads * head_dim)) * s
               ).astype(dtype),
        "wk": (jax.random.normal(k2, (d_model, n_kv * head_dim)) * s
               ).astype(dtype),
        "wv": (jax.random.normal(k3, (d_model, n_kv * head_dim)) * s
               ).astype(dtype),
        "wo": (jax.random.normal(k4, (n_heads * head_dim, d_model)) * s
               ).astype(dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    return p


def _attend(q, k, v, cfg, *, kv_cache, cache_len, causal, window=None,
            latent=False):
    """Attention of q (B, Hq, S, D) over k and v, on the path
    ``attention_path`` gives: against the KV cache (updated at
    ``cache_len``) when decoding, the fused kernels, or the fallback.
    Returns (out, new_kv), new_kv None without a cache."""
    s = q.shape[2]
    path = attention_path(s, kv_cache=kv_cache is not None, window=window,
                          latent=latent)
    if kv_cache is not None:
        ck, cv = kv_cache
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, 0, cache_len, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, 0, cache_len, 0))
        return decode_attention(q, ck, cv, cache_len + s,
                                window=window), (ck, cv)
    if path == "fused":
        if latent:
            return latent_attention(q, k, v), None
        return train_attention(q, k, v, causal=causal), None
    # off the fused path (attention_path names why): the impl registered
    # for "attention", else flash_attention_ref, the jnp flash attention
    # with a linear-memory fwd AND bwd (custom VJP)
    from .attention import flash_attention_ref
    attn = get_impl("attention", flash_attention_ref)
    kw = ({"unroll": True}
          if getattr(cfg, "unroll_scans", False)
          and attn is flash_attention_ref else {})
    return attn(q, k, v, causal=causal, window=window, **kw), None


def attention_block(p, x, cfg, *, positions=None, mrope_positions=None,
                    kv_cache=None, cache_len=None, causal=True,
                    window=None):
    """Returns (out, new_kv) where kv_cache is (k, v) of shape
    (B, Hkv, Smax, D) when decoding, else None.  A config with a latent
    rank runs ``mla_block``."""
    if cfg.kv_lora_rank:
        return mla_block(p, x, cfg, kv_cache=kv_cache, cache_len=cache_len,
                         causal=causal)
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    if _AXIS_MAP.get("attn_tp"):
        # tensor-parallel attention: heads over the model axis (falls
        # back to replicated on non-divisible head counts)
        q = constrain(q, "dp", "tp", None, None)
        k = constrain(k, "dp", "tp", None, None)
        v = constrain(v, "dp", "tp", None, None)
    else:
        # context-parallel attention: q sharded over seq ('sp'), full KV
        # gathered per shard — avoids the head-divisibility problem
        # (e.g. 40 heads on a 16-way axis), keeps flash transients local
        q = constrain(q, "dp", None, "sp", None)
        k = constrain(k, "dp", None, None, None)
        v = constrain(v, "dp", None, None, None)
    if positions is None:
        base = 0 if cache_len is None else cache_len
        positions = jnp.arange(s) + base
    if cfg.mrope:
        mp = (mrope_positions if mrope_positions is not None
              else default_mrope_positions(b, s) + (
                  0 if cache_len is None else cache_len))
        q = apply_mrope(q, mp, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, mp, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out, new_cache = _attend(q, k, v, cfg, kv_cache=kv_cache,
                             cache_len=cache_len, causal=causal,
                             window=window)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2/V3 MLA, no query compression)
# ---------------------------------------------------------------------------

def init_mla(key, d_model: int, n_heads: int, kv_lora_rank: int,
             qk_nope: int, qk_rope: int, v_dim: int, dtype) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def mat(k, fan_in, fan_out):
        return (jax.random.normal(k, (fan_in, fan_out)) * fan_in ** -0.5
                ).astype(dtype)
    return {"wq": mat(k1, d_model, n_heads * (qk_nope + qk_rope)),
            "wkv_a": mat(k2, d_model, kv_lora_rank + qk_rope),
            "kv_norm": jnp.ones((kv_lora_rank,), dtype),
            "wkv_b": mat(k3, kv_lora_rank, n_heads * (qk_nope + v_dim)),
            "wo": mat(k4, n_heads * v_dim, d_model)}


def mla_block(p, x, cfg, *, kv_cache=None, cache_len=None, causal=True):
    """Latent attention: q = x·W_q, heads of [nope | rope]; x·W_kva is
    the latent c (``kv_lora_rank``) and one rope key all heads share; c,
    normed, times W_kvb gives each head's nope key and value.  Rope turns
    the rope dims alone; scores are scaled by (nope + rope)^-1/2.
    Returns (out, new_kv) like ``attention_block``; the cache holds the
    expanded per-head keys (B, H, Smax, nope + rope) and values."""
    b, s, _ = x.shape
    h, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank
    q = (x @ p["wq"]).reshape(b, s, h, dn + dr).transpose(0, 2, 1, 3)
    kva = x @ p["wkv_a"]
    c = rmsnorm(kva[..., :r], p["kv_norm"], cfg.rms_norm_eps)
    kv = (c @ p["wkv_b"]).reshape(b, s, h, dn + dv).transpose(0, 2, 1, 3)
    base = 0 if cache_len is None else cache_len
    positions = jnp.arange(s) + base
    q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    k_rope = apply_rope(kva[:, None, :, r:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (b, h, s, dr))], -1)
    v = kv[..., dn:]
    out, new_cache = _attend(q, k, v, cfg, kv_cache=kv_cache,
                             cache_len=cache_len, causal=causal, latent=True)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, act: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s = d_model ** -0.5
    p = {"w_up": (jax.random.normal(k1, (d_model, d_ff)) * s).astype(dtype),
         "w_down": (jax.random.normal(k2, (d_ff, d_model))
                    * d_ff ** -0.5).astype(dtype)}
    if act == "swiglu":
        p["w_gate"] = (jax.random.normal(k3, (d_model, d_ff)) * s
                       ).astype(dtype)
    return p


def mlp_block(p, x, act: str = "swiglu"):
    if act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE (shared + routed experts, top-k, GShard-style static dispatch)
# ---------------------------------------------------------------------------

def init_moe(key, d_model: int, d_expert: int, n_experts: int,
             n_shared: int, act: str, dtype, n_held: int = 0,
             router_bias: bool = False) -> dict:
    """The router over all ``n_experts``; the matrices of ``n_held`` of
    them (all where 0); with ``router_bias`` the router's correction
    bias (one float32 per expert, used in choosing, never trained)."""
    keys = jax.random.split(key, 4)
    s = d_model ** -0.5
    held = n_held or n_experts
    p = {
        "router": (jax.random.normal(keys[0], (d_model, n_experts)) * s
                   ).astype(jnp.float32),
        # routed experts, stacked: (E, d_model, d_expert)…
        "we_up": (jax.random.normal(keys[1],
                  (held, d_model, d_expert)) * s).astype(dtype),
        "we_down": (jax.random.normal(keys[2],
                    (held, d_expert, d_model))
                    * d_expert ** -0.5).astype(dtype),
    }
    if act == "swiglu":
        p["we_gate"] = (jax.random.normal(keys[3],
                        (held, d_model, d_expert)) * s).astype(dtype)
    if router_bias:
        p["bias"] = jnp.zeros((n_experts,), jnp.float32)
    if n_shared:
        p["shared"] = init_mlp(jax.random.fold_in(key, 7), d_model,
                               d_expert * n_shared, act, dtype)
    return p


def moe_gmm_ref(x, w):
    """Grouped matmul reference: x (E, cap, d) @ w (E, d, f)."""
    return jnp.einsum("ecd,edf->ecf", x, w)


def moe_expert_mm(x_e, p, act: str):
    """Expert computation on pre-dispatched tokens.
    x_e: (E, cap, d_model) -> (E, cap, d_model)."""
    gmm = get_impl("moe_gmm", moe_gmm_ref)
    if act == "swiglu":
        h = jax.nn.silu(gmm(x_e, p["we_gate"])) * gmm(x_e, p["we_up"])
    else:
        h = jax.nn.gelu(gmm(x_e, p["we_up"]))
    return gmm(h, p["we_down"])


def _router(p, xt, top_k):
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)            # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, gate_idx


def _dispatch_groups(b: int, s: int, target: int = 1024) -> int:
    """Number of sequence chunks per row so that b*n_sc ~ target groups
    (>= the mesh size, so the group dim shards over every axis)."""
    n_sc = 1
    while (b * n_sc * 2 <= target and s % (n_sc * 2) == 0
           and s // (n_sc * 2) >= 64):
        n_sc *= 2
    return n_sc


def moe_block(p, x, *, n_experts: int, top_k: int, act: str = "swiglu",
              capacity_factor: float = 1.25):
    """Token-choice top-k MoE with static capacity and **grouped local
    dispatch**: tokens are split into G groups (batch x seq-chunks, the
    group dim sharded over every mesh axis), each group argsorts its own
    tokens and gathers them into a per-group (E, cap_g, D) buffer with
    purely LOCAL indices (vmapped over groups), so the SPMD partitioner
    never sees a data-dependent access to a sharded dim.  The expert
    matmul then runs with experts over tp and group-capacity rows over
    dp — the single resharding between those layouts IS the EP
    all-to-all.  Per-(group,expert) capacity mirrors real per-peer a2a
    buffers.  x: (B, S, D)."""
    b, s, d = x.shape
    K, E = top_k, n_experts
    n_sc = _dispatch_groups(b, s)
    G = b * n_sc
    Tg = s // n_sc
    xt = x.reshape(G, Tg, d)
    # one consistent layout throughout the block: groups over dp,
    # experts over tp.  (Going 'dpt'-sharded here and resharding to
    # (dp, tp) at the matmul makes GSPMD's backward transposes fall into
    # 'involuntary full rematerialization' — full replication.)
    xt = constrain(xt, "dp", None, None)
    probs, gate_vals, gate_idx = _router(p, xt.reshape(G * Tg, d), K)
    cap = max(1, int(capacity_factor * Tg * K / E))
    gate_g = gate_vals.reshape(G, Tg, K)
    eid_g = gate_idx.reshape(G, Tg, K)

    def route_one(eid):
        """eid: (Tg, K) -> (slot token idx (E*cap,), keep (E*cap,),
        slot gate-pos (E*cap,))  — all local to the group."""
        tk = Tg * K
        flat = eid.reshape(tk)
        order = jnp.argsort(flat, stable=True)
        eid_s = flat[order]
        seg = jnp.searchsorted(eid_s, jnp.arange(E), side="left")
        pos = jnp.arange(tk, dtype=jnp.int32) - seg[eid_s]
        keep_s = pos < cap
        slot = jnp.where(keep_s, eid_s * cap + pos, E * cap)
        # invert: for each slot, which (token,k) feeds it
        inv = jnp.full((E * cap + 1,), tk, jnp.int32).at[slot].set(order)
        inv = inv[:E * cap]
        filled = inv < tk
        tok_of_slot = jnp.where(filled, inv // K, 0)
        k_of_slot = jnp.where(filled, inv % K, 0)
        return tok_of_slot, k_of_slot, filled

    tok_slot, k_slot, filled = jax.vmap(route_one)(eid_g)  # (G, E*cap)

    # local gather into per-group expert buffers
    def gather_one(xt_g, tok_g, fill_g):
        return xt_g[tok_g] * fill_g[:, None].astype(xt_g.dtype)
    x_ge = jax.vmap(gather_one)(xt, tok_slot, filled)   # (G, E*cap, D)
    x_ge = x_ge.reshape(G, E, cap, d)
    # EP layout for the expert matmul: experts over tp, groups over dp
    x_ge = constrain(x_ge, "dp", "tp", None, None)
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", x_ge, p["we_gate"])) \
            * jnp.einsum("gecd,edf->gecf", x_ge, p["we_up"])
    else:
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", x_ge, p["we_up"]))
    h = constrain(h, "dp", "tp", None, None)
    y_ge = jnp.einsum("gecf,efd->gecd", h, p["we_down"])
    y_ge = constrain(y_ge, "dp", "tp", None, None)
    y_ge = y_ge.reshape(G, E * cap, d)

    # combine back to tokens with gate weights (local scatter-add)
    def combine_one(y_g, tok_g, k_g, fill_g, gates_g):
        gate_of_slot = gates_g[tok_g, k_g] * fill_g
        contrib = y_g * gate_of_slot[:, None].astype(y_g.dtype)
        return jnp.zeros((Tg, d), y_g.dtype).at[tok_g].add(contrib)
    y = jax.vmap(combine_one)(y_ge, tok_slot, k_slot,
                              filled.astype(jnp.float32), gate_g)
    y = constrain(y, "dp", None, None)
    if "shared" in p:
        y = y + jax.vmap(lambda xg: mlp_block(p["shared"], xg, act))(xt)
    aux = moe_aux_loss(probs, gate_idx, n_experts)
    return y.reshape(b, s, d), aux


def gmm_fused(m: int, k: int, n: int) -> bool:
    """Whether ``grouped_matmul`` runs its TPU kernels for ``m`` rows of
    ``k`` times ``k`` x ``n``: on one TPU (the compiler partitions no
    Pallas kernel), at shapes the kernels tile."""
    return (jax.default_backend() == "tpu" and _devices_spanned() == 1
            and gmm_tiling(m, k, n) is not None)


def row_ladder(t: int, k: int, n_held: int, n_experts: int) -> tuple:
    """The static sizes the held expert layer's row buffer may take, for
    ``t`` tokens choosing ``k`` of ``n_experts`` of which ``n_held`` are
    held: doubling from twice the expected rows (t·k·n_held/n_experts,
    rounded up to a multiple of 512) up to the worst case
    t·min(k, n_held), which is always the last."""
    top = t * min(k, n_held)
    expected = -(-t * k * n_held // n_experts)
    rung = 2 * (-(-expected // 512) * 512)
    rungs = []
    while rung < top:
        rungs.append(rung)
        rung *= 2
    return tuple(rungs) + (top,)


def _to_tokens(rows, src, valid, t: int, k: int):
    """Each token's sum of its rows in the buffer, in float32: ``rows``
    (R, D) added to their tokens.  ``src`` (R,) is each row's (token,
    k) pair, T·K-flat; rows where ``valid`` (R,) is false hold no pair
    and add nothing."""
    r = jnp.where(valid[:, None], rows.astype(jnp.float32), 0.0)
    return jnp.zeros((t, rows.shape[-1]), jnp.float32).at[src // k].add(r)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _take_rows(xt, src, valid, t: int, k: int):
    """The buffer's rows: xt's (T, D) token row for each of the R pairs
    ``src`` (T·K-flat, sorted by expert).  Its backward adds the valid
    rows back to their tokens (``_to_tokens``)."""
    return xt[src // k]


def _take_rows_fwd(xt, src, valid, t, k):
    return xt[src // k], (src, valid)


def _take_rows_bwd(t, k, res, ct):
    src, valid = res
    return _to_tokens(ct, src, valid, t, k).astype(ct.dtype), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _combine_rows(rows, weights, src, valid):
    """Each token's weighted sum of its rows in the buffer: rows (R, D)
    the experts' outputs, weights (T, K) float32, ``src`` (R,) each
    row's pair, ``valid`` (R,) whether a row holds a pair.  Returns
    (T, D) in the rows' dtype, summed in float32.  Its backward gathers
    the R rows' cotangents in the rows' dtype, where autodiff would
    gather them in float32."""
    t, k = weights.shape
    w = weights.reshape(-1)[src]
    return _to_tokens(rows * w[:, None], src, valid, t, k).astype(rows.dtype)


def _combine_rows_fwd(rows, weights, src, valid):
    return (_combine_rows(rows, weights, src, valid),
            (rows, weights, src, valid))


def _combine_rows_bwd(res, ct):
    rows, weights, src, valid = res
    t, k = weights.shape
    ct_rows = ct[src // k].astype(jnp.float32)
    w = jnp.where(valid, weights.reshape(-1)[src], 0.0)
    d_rows = (ct_rows * w[:, None]).astype(rows.dtype)
    d_w = jnp.where(valid, jnp.sum(ct_rows * rows.astype(jnp.float32), -1),
                    0.0)
    d_weights = jnp.zeros(t * k, jnp.float32).at[src].add(d_w)
    return d_rows, d_weights.reshape(t, k), None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def route(p, xt, *, top_k: int, router: str = "softmax",
          routed_scaling: float = 1.0):
    """The router of a dropless MoE layer, in float32 over every expert.
    ``softmax``: top-k of softmax(x·W_r).  ``sigmoid`` (DeepSeek-V3's
    ``noaux_tc``): s = sigmoid(x·W_r), the experts chosen by top-k of
    s + b (``bias``, which takes no gradient), weighted by s.  The k
    weights are normalised to sum 1, then multiplied by
    ``routed_scaling``.  xt (T, D) -> (probs (T, E),
    weights (T, K), experts (T, K))."""
    logits = jnp.matmul(xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if router == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        bias = jax.lax.stop_gradient(p["bias"])
        _, idx = jax.lax.top_k(probs + bias, top_k)
        weights = jnp.take_along_axis(probs, idx, -1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, top_k)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return probs, weights * routed_scaling, idx


def moe_block_held(p, x, *, n_experts: int, top_k: int, n_held: int = 0,
                   held_offset: int = 0, act: str = "swiglu",
                   router: str = "softmax", routed_scaling: float = 1.0):
    """Dropless MoE over the experts this device holds: ``n_held``
    experts from ``held_offset`` (all where ``n_held`` is 0), routed over
    all ``n_experts``.  Every (token, k) pair is sorted by expert; the
    held experts' pairs, one run of that order, are gathered into a
    buffer of R rows, go through ``grouped_matmul`` and come back
    weighted; pairs routed to experts held elsewhere add nothing here,
    as on one chip of an expert-parallel layer before its exchange.  R
    is the smallest size of ``row_ladder`` that holds the step's count
    of held pairs, chosen on the device (``lax.switch``), so no row is
    dropped and no capacity is set; the rows past the count belong to no
    held expert and reach nothing.  The shared experts, which every
    device computes, are added once.  Router, top-k, sort, the gather
    and the combine run under the scope ``moe_route``.

    x (B, S, D) -> (y, stats): ``aux`` the Switch load-balancing loss
    for a softmax router (0 for sigmoid, which has none), and over the
    held experts ``moe_rows_held`` (the rows computed),
    ``moe_rows_buffered`` (R) and ``moe_load_max_over_mean`` (the
    busiest expert's rows over the mean)."""
    b, s, d = x.shape
    t, kk = b * s, top_k
    held = n_held or n_experts
    xt = x.reshape(t, d)
    with jax.named_scope("moe_route"):
        probs, weights, idx = route(p, xt, top_k=kk, router=router,
                                    routed_scaling=routed_scaling)
        flat = idx.reshape(t * kk)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        sizes = jnp.sum(flat[:, None] == jnp.arange(n_experts), axis=0,
                        dtype=jnp.int32)
        counts = sizes[held_offset:held_offset + held]
        before = jnp.sum(sizes[:held_offset])
        n = jnp.sum(counts)
    f = p["we_up"].shape[-1]

    def rows(r, xt, weights, order, counts, before, n, we):
        with jax.named_scope("moe_route"):
            src = jnp.take(order, before + jnp.arange(r), mode="clip")
            valid = jnp.arange(r) < n
            x_rows = _take_rows(xt, src, valid, t, kk)
        # the rows past the count form one group that no held expert owns
        sizes_r = jnp.concatenate([counts, (r - n)[None]])
        fused = gmm_fused(r, d, f) and gmm_fused(r, f, d)

        def gmm(a, name):
            return grouped_matmul(a, we[name], sizes_r, 0, fused=fused)
        if act == "swiglu":
            hid = jax.nn.silu(gmm(x_rows, "we_gate")) * gmm(x_rows, "we_up")
        else:
            hid = jax.nn.gelu(gmm(x_rows, "we_up"))
        y_rows = gmm(hid, "we_down")
        with jax.named_scope("moe_route"):
            return _combine_rows(y_rows, weights, src, valid)

    ladder = row_ladder(t, kk, held, n_experts)
    we = {k: p[k] for k in ("we_gate", "we_up", "we_down") if k in p}
    args = (xt, weights, order, counts, before, n, we)
    if len(ladder) == 1:
        rung = jnp.zeros((), jnp.int32)
        y = rows(ladder[0], *args)
    else:
        # each size's branch recomputes its rows in the backward: the
        # gradient of an un-rematerialised switch has every branch return
        # every other branch's residuals, zero-filled, which at Moonlight's
        # shapes nearly doubles the step's temporaries
        branches = [jax.checkpoint(functools.partial(rows, r))
                    for r in ladder]
        with jax.named_scope("moe_route"):
            rung = jnp.sum(n > jnp.asarray(ladder[:-1]), dtype=jnp.int32)
        y = jax.lax.switch(rung, branches, *args)
    if "shared" in p:
        y = y + mlp_block(p["shared"], xt, act)
    counts = counts.astype(jnp.float32)
    stats = {"aux": (jnp.zeros((), jnp.float32) if router == "sigmoid"
                     else moe_aux_loss(probs, idx, n_experts)),
             "moe_rows_held": jnp.sum(counts),
             "moe_rows_buffered": jnp.asarray(ladder, jnp.float32)[rung],
             "moe_load_max_over_mean": jnp.max(counts)
             / jnp.maximum(jnp.mean(counts), 1e-9)}
    return y.reshape(b, s, d), stats


def moe_block_ep(p, x, *, n_experts: int, top_k: int, act: str = "swiglu",
                 capacity_factor: float = 1.25, mesh=None,
                 dp_axes=("data",), tp_axis: str = "model"):
    """True expert-parallel MoE with explicit `lax.all_to_all` dispatch
    inside shard_map (DeepSeek/DeepEP-style, the paper's Figure 1 EP).

    Each device routes its LOCAL tokens, packs per-destination-rank send
    buffers (rank r owns experts [r*E_loc, (r+1)*E_loc)), all-to-alls
    tokens + routing metadata over the tp axis, computes its local
    experts, and all-to-alls results back for the gated combine.  Unlike
    the pjit-auto grouped dispatch (moe_block), tokens are never
    replicated across tp and the combine is a2a, not an all-reduce —
    per-device traffic drops from O(T*d) to O(T*K*d/tp).
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    K, E = top_k, n_experts
    tp = mesh.shape[tp_axis]
    E_loc = E // tp
    dp_size = int(np.prod([mesh.shape[a] for a in dp_axes]))
    t_loc = (b // dp_size) * (s // tp)        # local tokens per device
    cap_send = max(1, int(capacity_factor * t_loc * K / tp))
    cap_e = max(1, int(capacity_factor * t_loc * K / E_loc))

    def body(xl, router, wg, wu, wd):
        bl, sl, _ = xl.shape
        tl = bl * sl
        xt = xl.reshape(tl, d)
        probs = jax.nn.softmax(
            xt.astype(jnp.float32) @ router[0].astype(jnp.float32), -1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)       # (tl, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        tk = tl * K
        eid = gate_idx.reshape(tk)
        tok = jnp.arange(tk, dtype=jnp.int32) // K
        dest = eid // E_loc                                  # (tk,)
        order = jnp.argsort(dest, stable=True)
        dest_s = dest[order]
        seg = jnp.searchsorted(dest_s, jnp.arange(tp), side="left")
        pos = jnp.arange(tk, dtype=jnp.int32) - seg[dest_s]
        keep = pos < cap_send
        slot = jnp.where(keep, dest_s * cap_send + pos, tp * cap_send)

        send_x = jnp.zeros((tp * cap_send + 1, d), xt.dtype
                           ).at[slot].set(xt[tok[order]])
        send_le = jnp.full((tp * cap_send + 1,), E_loc, jnp.int32
                           ).at[slot].set(eid[order] % E_loc)
        # remember where each send slot came from, for the combine
        tok_of_slot = jnp.full((tp * cap_send + 1,), tl, jnp.int32
                               ).at[slot].set(tok[order])
        gate_of_slot = jnp.zeros((tp * cap_send + 1,), jnp.float32
                                 ).at[slot].set(
            gate_vals.reshape(tk)[order] * keep)

        sx = send_x[:-1].reshape(tp, cap_send, d)
        sle = send_le[:-1].reshape(tp, cap_send)
        rx = jax.lax.all_to_all(sx, tp_axis, 0, 0, tiled=False)
        rle = jax.lax.all_to_all(sle, tp_axis, 0, 0, tiled=False)

        # local expert compute on received tokens
        tr = tp * cap_send
        xr = rx.reshape(tr, d)
        er = rle.reshape(tr)                                 # E_loc = drop
        order2 = jnp.argsort(er, stable=True)
        er_s = er[order2]
        seg2 = jnp.searchsorted(er_s, jnp.arange(E_loc), side="left")
        pos2 = jnp.arange(tr, dtype=jnp.int32) - seg2[er_s]
        keep2 = (pos2 < cap_e) & (er_s < E_loc)
        slot2_s = jnp.where(keep2, er_s * cap_e + pos2, E_loc * cap_e)
        slot_of_recv = jnp.zeros((tr,), jnp.int32).at[order2].set(slot2_s)

        buf = jnp.zeros((E_loc * cap_e + 1, d), xt.dtype
                        ).at[slot_of_recv].add(xr)
        x_e = buf[:E_loc * cap_e].reshape(E_loc, cap_e, d)
        if act == "swiglu":
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_e, wg)) * \
                jnp.einsum("ecd,edf->ecf", x_e, wu)
        else:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x_e, wu))
        y_e = jnp.einsum("ecf,efd->ecd", h, wd)
        y_flat = jnp.concatenate(
            [y_e.reshape(E_loc * cap_e, d),
             jnp.zeros((1, d), y_e.dtype)], axis=0)
        y_r = y_flat[slot_of_recv]                           # (tr, d)

        y_back = jax.lax.all_to_all(
            y_r.reshape(tp, cap_send, d), tp_axis, 0, 0, tiled=False)
        # combine at the source with the stashed gates
        contrib = y_back.reshape(tp * cap_send, d) * \
            gate_of_slot[:-1, None].astype(y_back.dtype)
        y_tok = jnp.zeros((tl + 1, d), xt.dtype
                          ).at[tok_of_slot[:-1]].add(contrib)[:tl]

        # load-balance aux: global means via psum over every mesh axis
        all_axes = tuple(dp_axes) + (tp_axis,)
        n_tok_g = jax.lax.psum(jnp.float32(tl), all_axes)
        sum_probs = jax.lax.psum(probs.sum(0), all_axes)     # (E,)
        top1 = jax.nn.one_hot(gate_idx[:, 0], E).sum(0)
        sum_top1 = jax.lax.psum(top1, all_axes)
        aux = E * jnp.sum((sum_probs / n_tok_g) * (sum_top1 / n_tok_g))
        return y_tok.reshape(bl, sl, d), aux

    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, tp_axis, None),      # x: batch@dp, seq@tp
                  P(None, None, None),       # router (wrapped, see call)
                  P(tp_axis, None, None),    # we_gate
                  P(tp_axis, None, None),    # we_up
                  P(tp_axis, None, None)),   # we_down
        out_specs=(P(dp, tp_axis, None), P()),
        check_vma=False)
    router = p["router"][None]               # add a dummy leading axis
    wg = p.get("we_gate", p["we_up"])
    y, aux = f(x, router, wg, p["we_up"], p["we_down"])
    if "shared" in p:
        y = y + mlp_block(p["shared"], x, act)
    return y, aux


def moe_block_dense(p, x, *, n_experts: int, top_k: int,
                    act: str = "swiglu", capacity_factor: float = 1.25):
    """GShard-style one-hot dispatch einsums — O(T·K·E·cap) memory, only
    usable at toy scale; serves as the oracle for the sort-based path."""
    b, s, d = x.shape
    n_tok = b * s
    xt = x.reshape(n_tok, d)
    probs, gate_vals, gate_idx = _router(p, xt, top_k)
    cap = max(1, int(capacity_factor * n_tok * top_k / n_experts))
    onehot = jax.nn.one_hot(gate_idx, n_experts, dtype=jnp.int32)
    flat = onehot.reshape(n_tok * top_k, n_experts)
    pos_in_e = jnp.cumsum(flat, axis=0) * flat - 1
    pos = pos_in_e.reshape(n_tok, top_k, n_experts)
    keep = (pos < cap) & (onehot > 0)
    pos_c = jnp.clip(pos, 0, cap - 1)
    disp = (jax.nn.one_hot(pos_c, cap, dtype=xt.dtype)
            * keep[..., None].astype(xt.dtype))
    disp_t = disp.sum(1)
    x_e = jnp.einsum("tec,td->ecd", disp_t, xt)
    y_e = moe_expert_mm(x_e, p, act)
    comb = (disp * gate_vals[..., None, None].astype(xt.dtype)).sum(1)
    y = jnp.einsum("tec,ecd->td", comb, y_e)
    if "shared" in p:
        y = y + mlp_block(p["shared"], xt, act)
    aux = moe_aux_loss(probs, gate_idx, n_experts)
    return y.reshape(b, s, d), aux


def moe_aux_loss(probs, gate_idx, n_experts: int) -> jax.Array:
    """Switch-style load-balancing loss."""
    me = probs.mean(axis=0)
    top1 = jax.nn.one_hot(gate_idx[:, 0], n_experts).mean(axis=0)
    return n_experts * jnp.sum(me * top1)


# ---------------------------------------------------------------------------
# Mamba (1 and 2) — selective SSM
# ---------------------------------------------------------------------------

def init_mamba(key, d_model: int, state: int, version: int, dtype,
               expand: int = 2, d_conv: int = 4, headdim: int = 64) -> dict:
    d_inner = expand * d_model
    ks = jax.random.split(key, 8)
    s = d_model ** -0.5
    p = {
        "in_proj": (jax.random.normal(ks[0], (d_model, 2 * d_inner)) * s
                    ).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (d_conv, d_inner)) * 0.2
                   ).astype(dtype),
        "conv_b": jnp.zeros((d_inner,), dtype),
        "out_proj": (jax.random.normal(ks[2], (d_inner, d_model))
                     * d_inner ** -0.5).astype(dtype),
    }
    if version == 1:
        dt_rank = max(1, d_model // 16)
        p.update({
            "x_proj": (jax.random.normal(ks[3],
                       (d_inner, dt_rank + 2 * state)) * s).astype(dtype),
            "dt_proj": (jax.random.normal(ks[4], (dt_rank, d_inner))
                        * dt_rank ** -0.5).astype(dtype),
            "dt_bias": jnp.zeros((d_inner,), dtype),
            "A_log": jnp.log(jnp.broadcast_to(
                jnp.arange(1, state + 1, dtype=jnp.float32),
                (d_inner, state))).astype(jnp.float32),
            "D": jnp.ones((d_inner,), jnp.float32),
        })
    else:  # mamba2 (SSD): scalar A per head
        n_heads = d_inner // headdim
        p.update({
            "bc_proj": (jax.random.normal(ks[3], (d_inner, 2 * state)) * s
                        ).astype(dtype),
            "dt_bias": jnp.zeros((n_heads,), jnp.float32),
            "A_log": jnp.zeros((n_heads,), jnp.float32),
            "D": jnp.ones((n_heads,), jnp.float32),
            "dt_proj2": (jax.random.normal(ks[4], (d_inner, n_heads))
                         * s).astype(dtype),
        })
    return p


def _causal_conv(x, w, b, state=None):
    """x: (B, S, C), w: (K, C). Returns (y, new_state (B, K-1, C))."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=1)
    new_state = xp[:, -(k - 1):] if k > 1 else None
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return y + b, new_state


SSM_CHUNK = 128


def _pick_chunk(s: int, chunk: int) -> int:
    if s <= chunk:
        return s
    while s % chunk:
        chunk //= 2
    return max(chunk, 1)


def ssm_scan_ref(xz, dt, A, B, C, D, h0=None, chunk: int = SSM_CHUNK,
                 unroll_chunks: bool = False):
    """Selective scan (mamba1 core), chunked for linear backward memory.

    xz: (B,S,C) inputs; dt: (B,S,C); A: (C,N); B,C: (B,S,N); D: (C,)
    Returns (y (B,S,C), last_state (B,C,N)).

    The sequence is processed in checkpointed chunks: the outer scan
    saves only the chunk-boundary states for autodiff, and the decay
    terms exp(dt*A) are built per-step inside the chunk so a
    (B,S,C,N) tensor is never materialized — the same structure as the
    chunked Mamba kernel (kernels/mamba_scan.py uses this as oracle)."""
    b, s, c = xz.shape
    n = A.shape[1]
    if h0 is None:
        h0 = jnp.zeros((b, c, n), jnp.float32)
    q = _pick_chunk(s, chunk)
    nc = s // q

    def chunk_body(h, inp):
        xc, dtc, Bc, Cc = inp                 # (q,B,·)

        def step(h, t_inp):
            x_t, dt_t, B_t, C_t = t_inp       # (B,C) (B,C) (B,N) (B,N)
            dA_t = jnp.exp(dt_t[..., None] * A)          # (B,C,N)
            h = h * dA_t + (dt_t * x_t)[..., None] * B_t[:, None, :]
            y = jnp.einsum("bcn,bn->bc", h, C_t.astype(jnp.float32))
            return h, y

        h, ys = jax.lax.scan(step, h,
                             (xc.astype(jnp.float32),
                              dtc.astype(jnp.float32),
                              Bc.astype(jnp.float32),
                              Cc.astype(jnp.float32)))
        return h, ys

    xc = jnp.moveaxis(xz.reshape(b, nc, q, c), 1, 0).swapaxes(1, 2)
    dtc = jnp.moveaxis(dt.reshape(b, nc, q, c), 1, 0).swapaxes(1, 2)
    Bc = jnp.moveaxis(B.reshape(b, nc, q, n), 1, 0).swapaxes(1, 2)
    Cc = jnp.moveaxis(C.reshape(b, nc, q, n), 1, 0).swapaxes(1, 2)
    body = jax.checkpoint(chunk_body)
    hT, ys = jax.lax.scan(body, h0.astype(jnp.float32),
                          (xc, dtc, Bc, Cc), unroll=unroll_chunks)
    # ys: (nc, q, B, C) -> (B, S, C)
    y = ys.reshape(nc * q, b, c).swapaxes(0, 1).reshape(b, s, c)
    y = y.astype(xz.dtype) + xz * D.astype(xz.dtype)
    return y, hT


def mamba_block(p, x, *, state: int, version: int, conv_state=None,
                ssm_state=None, headdim: int = 64,
                unroll_chunks: bool = False, chunk: int = SSM_CHUNK):
    """Full Mamba block.  When conv_state/ssm_state are given (decode),
    processes S tokens incrementally and returns updated states."""
    b, s, d = x.shape
    xz = x @ p["in_proj"]
    xh, z = jnp.split(xz, 2, axis=-1)                   # (B,S,Ci)
    # SSM recurrence is independent per channel: shard d_inner over tp
    # (the sequence dim must stay whole for the scan)
    xh = constrain(xh, "dp", None, "tp")
    z = constrain(z, "dp", None, "tp")
    xh, new_conv = _causal_conv(xh, p["conv_w"], p["conv_b"], conv_state)
    xh = jax.nn.silu(xh)
    ci = xh.shape[-1]
    if version == 1:
        proj = xh @ p["x_proj"]
        dt_rank = p["dt_proj"].shape[0]
        dt, Bm, Cm = jnp.split(proj, [dt_rank, dt_rank + state], axis=-1)
        dt = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        scan = get_impl("mamba_scan", ssm_scan_ref)
        kw = ({"unroll_chunks": unroll_chunks, "chunk": chunk}
              if scan is ssm_scan_ref else {})
        y, hT = scan(xh, dt, A, Bm, Cm, p["D"], h0=ssm_state, **kw)
    else:
        nh = ci // headdim
        bc = xh @ p["bc_proj"]
        Bm, Cm = jnp.split(bc, 2, axis=-1)              # (B,S,N)
        dt = jax.nn.softplus(xh @ p["dt_proj2"] + p["dt_bias"])  # (B,S,H)
        A = -jnp.exp(p["A_log"])                        # (H,)
        xh_h = xh.reshape(b, s, nh, headdim)
        y, hT = _ssd_scan(xh_h, dt, A, Bm, Cm, p["D"], ssm_state,
                          chunk=chunk, unroll_chunks=unroll_chunks)
        y = y.reshape(b, s, ci)
    y = y * jax.nn.silu(z)
    return y @ p["out_proj"], new_conv, hT


def _ssd_scan(x_h, dt, A, B, C, D, h0=None, chunk: int = SSM_CHUNK,
              unroll_chunks: bool = False):
    """Mamba2 SSD scan, chunked like ssm_scan_ref.
    x_h: (B,S,H,P); dt: (B,S,H); A: (H,); B,C: (B,S,N).
    State: (B,H,P,N)."""
    b, s, h, p_ = x_h.shape
    n = B.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((b, h, p_, n), jnp.float32)
    q = _pick_chunk(s, chunk)
    nc = s // q

    def chunk_body(hc, inp):
        xc, dtc, Bc, Cc = inp                # (q, B, ...)

        def step(hc, t_inp):
            x_t, dt_t, B_t, C_t = t_inp      # (B,H,P) (B,H) (B,N) (B,N)
            dA_t = jnp.exp(dt_t * A)         # (B,H)
            hc = hc * dA_t[..., None, None] + jnp.einsum(
                "bhp,bn->bhpn", x_t * dt_t[..., None], B_t)
            y = jnp.einsum("bhpn,bn->bhp", hc, C_t)
            return hc, y

        hc, ys = jax.lax.scan(step, hc,
                              (xc.astype(jnp.float32),
                               dtc.astype(jnp.float32),
                               Bc.astype(jnp.float32),
                               Cc.astype(jnp.float32)))
        return hc, ys

    def to_chunks(a, feat_shape):
        return jnp.moveaxis(a.reshape((b, nc, q) + feat_shape), 1, 0
                            ).swapaxes(1, 2)

    xc = to_chunks(x_h, (h, p_))
    dtc = to_chunks(dt, (h,))
    Bc = to_chunks(B, (n,))
    Cc = to_chunks(C, (n,))
    body = jax.checkpoint(chunk_body)
    hT, ys = jax.lax.scan(body, h0.astype(jnp.float32),
                          (xc, dtc, Bc, Cc), unroll=unroll_chunks)
    y = ys.reshape(nc * q, b, h, p_).swapaxes(0, 1).reshape(b, s, h, p_)
    y = y.astype(x_h.dtype) + x_h * D[None, None, :, None].astype(
        x_h.dtype)
    return y, hT
