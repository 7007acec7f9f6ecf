"""Unified architecture definition: one ArchConfig drives dense / MoE /
SSM / hybrid / enc-dec / VLM model families (DESIGN.md §4).

Layers are stacked (leading ``n_layers`` axis) and applied under
``lax.scan`` so the lowered HLO stays small at 64-layer scale, and the
whole stack shards under pjit.  Training remat is per-layer
(``jax.checkpoint`` around the scan body, policy configurable).

A decoder layer's attention and its MLP or expert block, each with its
pre-norm, run under ``jax.named_scope("attn")`` and ``("mlp")``; an
expert block nests ``("moe")`` inside ``mlp``, and its routing
``("moe_route")`` inside that.  The LM head with the cross-entropy runs
under ``("lm_head_ce")``.  The names reach the compiled ops' metadata,
where a device trace finds them (forward, remat recompute and backward
alike).

An MoE stack may lead with ``n_dense_layers`` dense layers (width
``d_ff``): they stack apart, in ``params["dense_layers"]``, and run
before ``params["layers"]``.

Public entry points (all pure):
  init(cfg, key)                         -> params
  train_loss(cfg, params, batch)         -> scalar loss
  train_loss_and_stats(cfg, params, batch) -> (loss, expert-load stats)
  prefill(cfg, params, tokens, …)        -> (logits, cache)
  decode_step(cfg, params, token, cache) -> (logits, cache)
  init_cache(cfg, batch, max_seq)        -> cache pytree
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import layers as L


@dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    n_shared: int = 0
    d_expert: int = 0          # per-expert FFN width
    capacity_factor: float = 1.25
    # router: "softmax", or "sigmoid" (DeepSeek-V3 noaux_tc: top-k of
    # sigmoid scores plus a correction bias, weighted by the scores); the
    # k weights are normalised, then scaled by routed_scaling
    router: str = "softmax"
    routed_scaling: float = 1.0
    # the experts this device holds, n_held from held_offset (0 = all):
    # one chip's share of an expert-parallel layer
    n_held: int = 0
    held_offset: int = 0


@dataclass(frozen=True)
class SSMCfg:
    state: int
    version: int = 1           # 1 = mamba1, 2 = mamba2
    d_conv: int = 4
    expand: int = 2
    headdim: int = 64


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                # dense | moe | ssm | audio | vlm | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0          # 0 -> d_model // n_heads
    rms_norm_eps: float = 1e-6
    # multi-head latent attention (DeepSeek-V2/V3): a latent KV rank, 0 =
    # none; query/key heads of nope + rope dims, values of v_head_dim
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # leading dense layers (width d_ff) of an MoE stack; n_layers counts
    # them
    n_dense_layers: int = 0
    qkv_bias: bool = False
    rope: bool = True
    rope_theta: float = 1e4
    mrope: bool = False
    mrope_sections: tuple = (16, 24, 24)
    norm: str = "rmsnorm"
    act: str = "swiglu"
    tie_embeddings: bool = False
    moe: Optional[MoECfg] = None
    ssm: Optional[SSMCfg] = None
    # hybrid (zamba2): one shared attention+mlp block applied every k
    # ssm layers (weights shared across applications)
    hybrid_every: int = 0
    # enc-dec (whisper)
    n_enc_layers: int = 0
    enc_seq: int = 1500        # precomputed frame embeddings (stub)
    causal: bool = True
    # sub-quadratic decode support (long_500k): SSM/hybrid only
    subquadratic: bool = False
    sliding_window: int = 0    # hybrid decode attn window (0 = full)
    dtype: str = "bfloat16"
    remat: str = "full"        # none | full
    # chunked cross-entropy: compute logits `loss_chunk` tokens at a time
    # (a (B,S,vocab) logits tensor at 1M tokens x 152k vocab would be
    # hundreds of GB/device even sharded)
    loss_chunk: int = 0
    # fully unroll scans (cost-probe compiles: XLA cost_analysis counts
    # rolled while-loop bodies once, so FLOPs/bytes need explicit
    # iterations; never used for real execution)
    unroll_scans: bool = False
    # SSM scan chunk length (memory/recompute tradeoff knob)
    ssm_chunk: int = 128
    # source metadata
    source: str = ""

    def __post_init__(self):
        if isinstance(self.moe, dict):
            object.__setattr__(self, "moe", MoECfg(**self.moe))
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim",
                               self.d_model // max(self.n_heads, 1))

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def param_count(self) -> int:
        return params_count(self)

    def active_param_count(self) -> int:
        return params_count(self, active_only=True)

    def reduced(self, n_layers=2, d_model=64, d_ff=128, vocab=256,
                n_heads=4, n_kv_heads=None, dtype="float32") -> "ArchConfig":
        """Small same-family config for CPU smoke tests."""
        kw: dict[str, Any] = dict(
            n_layers=n_layers, d_model=d_model, d_ff=d_ff, vocab=vocab,
            n_heads=n_heads, head_dim=d_model // n_heads,
            n_kv_heads=(n_kv_heads if n_kv_heads is not None
                        else max(1, min(self.n_kv_heads, n_heads))),
            dtype=dtype, remat="none")
        if self.moe:
            kw["moe"] = dataclasses.replace(
                self.moe, n_experts=4, top_k=min(2, self.moe.top_k),
                n_shared=min(1, self.moe.n_shared), d_expert=d_ff // 2,
                n_held=min(2, self.moe.n_held), held_offset=0)
        if self.kv_lora_rank:
            hd = d_model // n_heads
            kw.update(kv_lora_rank=d_model // 4, qk_nope_head_dim=hd,
                      qk_rope_head_dim=hd // 2, v_head_dim=hd)
        if self.n_dense_layers:
            kw["n_dense_layers"] = 1
        if self.ssm:
            kw["ssm"] = SSMCfg(state=8, version=self.ssm.version,
                               headdim=16)
        if self.hybrid_every:
            kw["hybrid_every"] = 2
        if self.n_enc_layers:
            kw["n_enc_layers"] = 2
            kw["enc_seq"] = 16
        if self.mrope:
            half = (d_model // n_heads) // 2
            t = half // 4
            h = (half - t) // 2
            kw["mrope_sections"] = (t, h, half - t - h)
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# parameter counting (for roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------

def params_count(cfg: ArchConfig, active_only: bool = False) -> int:
    d, dff = cfg.d_model, cfg.d_ff
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    if cfg.qkv_bias:
        attn += (hq + 2 * hkv) * hd
    if cfg.kv_lora_rank:
        r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
        attn = (d * hq * (nope + rope) + d * (r + rope) + r
                + r * hq * (nope + dv) + hq * dv * d)
    n_mlp_mats = 3 if cfg.act == "swiglu" else 2
    n = 0
    if cfg.ssm:
        di = cfg.ssm.expand * d
        ssm = d * 2 * di + di * d                       # in/out proj
        ssm += cfg.ssm.d_conv * di + di                 # conv w + b
        if cfg.ssm.version == 1:
            dt_rank = max(1, d // 16)
            ssm += di * (dt_rank + 2 * cfg.ssm.state)   # x_proj
            ssm += dt_rank * di + di                    # dt_proj + bias
            ssm += di * cfg.ssm.state + di              # A_log + D
        else:
            nh = di // cfg.ssm.headdim
            ssm += di * 2 * cfg.ssm.state               # bc_proj
            ssm += di * nh + nh + nh + nh               # dt_proj2/bias/A/D
        ssm += d                                        # layer norm
        n += cfg.n_layers * ssm
        if cfg.hybrid_every:
            n += attn + n_mlp_mats * d * dff + 2 * d    # shared block
    else:
        per_layer = attn + 2 * d                        # norms
        if cfg.moe:
            e = cfg.moe
            per_expert = n_mlp_mats * d * e.d_expert
            router = d * e.n_experts + (e.n_experts if e.router == "sigmoid"
                                        else 0)
            moe_all = (e.n_held or e.n_experts) * per_expert + router
            moe_act = e.top_k * per_expert + router
            if e.n_shared:
                shared = n_mlp_mats * d * e.d_expert * e.n_shared
                moe_all += shared
                moe_act += shared
            dense = per_layer + n_mlp_mats * d * dff
            per_layer += moe_act if active_only else moe_all
            n += cfg.n_dense_layers * (dense - per_layer)
        else:
            per_layer += n_mlp_mats * d * dff
        n += cfg.n_layers * per_layer
        if cfg.n_enc_layers:
            n += cfg.n_enc_layers * (attn + n_mlp_mats * d * dff + 2 * d)
            n += cfg.n_layers * (attn + d)              # cross-attn
    n += cfg.vocab * d * (1 if cfg.tie_embeddings else 2)
    n += d                                              # final norm
    return n


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack(fn, key, n, *args):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: fn(k, *args))(keys)


def init(cfg: ArchConfig, key: jax.Array) -> dict:
    dt = cfg.jdtype
    keys = jax.random.split(key, 8)
    p: dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model))
                  * 0.02).astype(dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(keys[1],
                        (cfg.d_model, cfg.vocab)) * 0.02).astype(dt)

    if cfg.ssm and not cfg.hybrid_every:       # pure SSM (falcon-mamba)
        def one(k):
            return {
                "norm": jnp.ones((cfg.d_model,), dt),
                "mamba": L.init_mamba(k, cfg.d_model, cfg.ssm.state,
                                      cfg.ssm.version, dt,
                                      cfg.ssm.expand, cfg.ssm.d_conv,
                                      cfg.ssm.headdim)}
        p["layers"] = _stack(lambda k: one(k), keys[2], cfg.n_layers)
    elif cfg.hybrid_every:                     # zamba2-style hybrid
        def one(k):
            return {
                "norm": jnp.ones((cfg.d_model,), dt),
                "mamba": L.init_mamba(k, cfg.d_model, cfg.ssm.state,
                                      cfg.ssm.version, dt,
                                      cfg.ssm.expand, cfg.ssm.d_conv,
                                      cfg.ssm.headdim)}
        p["layers"] = _stack(lambda k: one(k), keys[2], cfg.n_layers)
        p["shared_attn"] = {
            "norm1": jnp.ones((cfg.d_model,), dt),
            "attn": L.init_attn(keys[3], cfg.d_model, cfg.n_heads,
                                cfg.n_kv_heads, cfg.head_dim,
                                cfg.qkv_bias, dt),
            "norm2": jnp.ones((cfg.d_model,), dt),
            "mlp": L.init_mlp(keys[4], cfg.d_model, cfg.d_ff, cfg.act, dt),
        }
    else:                                      # attention stacks
        def one(k, dense):
            k1, k2 = jax.random.split(k)
            if cfg.kv_lora_rank:
                attn = L.init_mla(k1, cfg.d_model, cfg.n_heads,
                                  cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                                  cfg.qk_rope_head_dim, cfg.v_head_dim, dt)
            else:
                attn = L.init_attn(k1, cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.head_dim,
                                   cfg.qkv_bias, dt)
            lp = {
                "norm1": jnp.ones((cfg.d_model,), dt),
                "attn": attn,
                "norm2": jnp.ones((cfg.d_model,), dt),
            }
            if cfg.moe and not dense:
                lp["moe"] = L.init_moe(k2, cfg.d_model, cfg.moe.d_expert,
                                       cfg.moe.n_experts,
                                       cfg.moe.n_shared, cfg.act, dt,
                                       n_held=cfg.moe.n_held,
                                       router_bias=cfg.moe.router
                                       == "sigmoid")
            else:
                lp["mlp"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff,
                                       cfg.act, dt)
            return lp
        p["layers"] = _stack(lambda k: one(k, False), keys[2],
                             cfg.n_layers - cfg.n_dense_layers)
        if cfg.n_dense_layers:
            p["dense_layers"] = _stack(lambda k: one(k, True), keys[7],
                                       cfg.n_dense_layers)
        if cfg.n_enc_layers:                   # whisper enc-dec
            def enc_one(k):
                k1, k2 = jax.random.split(k)
                return {
                    "norm1": jnp.ones((cfg.d_model,), dt),
                    "attn": L.init_attn(k1, cfg.d_model, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.head_dim,
                                        cfg.qkv_bias, dt),
                    "norm2": jnp.ones((cfg.d_model,), dt),
                    "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff,
                                      cfg.act, dt)}
            p["enc_layers"] = _stack(lambda k: enc_one(k), keys[5],
                                     cfg.n_enc_layers)

            def cross_one(k):
                return {
                    "norm": jnp.ones((cfg.d_model,), dt),
                    "attn": L.init_attn(k, cfg.d_model, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.head_dim,
                                        cfg.qkv_bias, dt)}
            p["cross_layers"] = _stack(lambda k: cross_one(k), keys[6],
                                       cfg.n_layers)
    return p


# ---------------------------------------------------------------------------
# forward stacks
# ---------------------------------------------------------------------------

def _norm(cfg, w, x):
    return L.rmsnorm(x, w, cfg.rms_norm_eps)


def _dec_layer(cfg, lp, x, enc_out=None, cross_lp=None,
               mrope_positions=None):
    if cfg.ssm:
        h, _, _ = L.mamba_block(lp["mamba"], _norm(cfg, lp["norm"], x),
                                state=cfg.ssm.state,
                                version=cfg.ssm.version,
                                headdim=cfg.ssm.headdim,
                                unroll_chunks=cfg.unroll_scans,
                                chunk=cfg.ssm_chunk)
        return x + h, {"aux": jnp.zeros((), jnp.float32)}
    with jax.named_scope("attn"):
        a, _ = L.attention_block(lp["attn"], _norm(cfg, lp["norm1"], x),
                                 cfg, mrope_positions=mrope_positions,
                                 causal=cfg.causal)
    x = x + a
    if cross_lp is not None:
        # cross attention: keys/values from the encoder output
        c = _cross_attn(cfg, cross_lp["attn"],
                        _norm(cfg, cross_lp["norm"], x), enc_out)
        x = x + c
    stats = {"aux": jnp.zeros((), jnp.float32)}
    with jax.named_scope("mlp"):
        if "moe" in lp:
            with jax.named_scope("moe"):
                m, stats = _moe_dispatch(cfg, lp["moe"],
                                         _norm(cfg, lp["norm2"], x))
        else:
            m = L.mlp_block(lp["mlp"], _norm(cfg, lp["norm2"], x), cfg.act)
    return x + m, stats


def _moe_dispatch(cfg, moe_params, h):
    """Choose the MoE implementation: explicit shard_map all-to-all EP
    when the launch layer requested it and the shapes divide; the
    dropless layer over the held experts (``moe_block_held``) where the
    config holds a share of them or the step runs on one device; else
    the pjit-auto grouped dispatch, which drops rows past its capacity.
    Returns (y, stats): ``aux`` the load-balancing loss, and the held
    layer's counters."""
    e = cfg.moe
    if e.n_held or L._devices_spanned() == 1:
        return L.moe_block_held(
            moe_params, h, n_experts=e.n_experts, top_k=e.top_k,
            n_held=e.n_held, held_offset=e.held_offset, act=cfg.act,
            router=e.router, routed_scaling=e.routed_scaling)
    y, aux = _moe_dispatch_capacity(cfg, moe_params, h)
    return y, {"aux": aux}


def _moe_dispatch_capacity(cfg, moe_params, h):
    kw = dict(n_experts=cfg.moe.n_experts, top_k=cfg.moe.top_k,
              act=cfg.act, capacity_factor=cfg.moe.capacity_factor)
    amap = L._AXIS_MAP
    mesh = amap.get("mesh")
    if amap.get("moe_a2a") and mesh is not None:
        import numpy as _np
        tp_axis = amap.get("tp")
        dp_axes = amap.get("dp")
        dp_axes = (dp_axes,) if isinstance(dp_axes, str) else tuple(dp_axes)
        tp = mesh.shape[tp_axis]
        dp = int(_np.prod([mesh.shape[a] for a in dp_axes]))
        b, s, _ = h.shape
        if (cfg.moe.n_experts % tp == 0 and s % tp == 0 and b % dp == 0):
            return L.moe_block_ep(moe_params, h, mesh=mesh,
                                  dp_axes=dp_axes, tp_axis=tp_axis, **kw)
    return L.moe_block(moe_params, h, **kw)


def _cross_attn(cfg, ap, x, enc_out):
    """Cross-attention: queries from x, keys/values from enc_out."""
    b, s, _ = x.shape
    se = enc_out.shape[1]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ ap["wq"]).reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
    k = (enc_out @ ap["wk"]).reshape(b, se, hkv, hd).transpose(0, 2, 1, 3)
    v = (enc_out @ ap["wv"]).reshape(b, se, hkv, hd).transpose(0, 2, 1, 3)
    from .attention import chunked_attention
    o = chunked_attention(q, k, v, causal=False)
    return o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd) @ ap["wo"]


def _run_decoder(cfg: ArchConfig, p: dict, x: jax.Array,
                 enc_out=None, mrope_positions=None):
    """x: (B, S, D) embedded inputs -> (hidden, aux_loss, stats): the
    held expert layers' counters (``moe_rows_held`` and
    ``moe_rows_buffered`` their means over the layers,
    ``moe_load_max_over_mean`` the largest), else {}."""
    if cfg.hybrid_every:
        return _run_hybrid(cfg, p, x) + ({},)

    have_cross = "cross_layers" in p

    def body(carry, lp):
        x = carry
        if have_cross:
            lp, cross_lp = lp
        else:
            cross_lp = None
        x, stats = _dec_layer(cfg, lp, x, enc_out=enc_out,
                              cross_lp=cross_lp,
                              mrope_positions=mrope_positions)
        x = L.constrain(x, "dp", "sp", None)
        return x, stats

    fn = body
    if cfg.remat == "full":
        fn = jax.checkpoint(body)
    dense_aux = None
    if "dense_layers" in p:
        x, dense = jax.lax.scan(fn, x, p["dense_layers"],
                                unroll=cfg.unroll_scans)
        dense_aux = jnp.sum(dense["aux"])
    xs = (p["layers"], p["cross_layers"]) if have_cross else p["layers"]
    x, stats = jax.lax.scan(fn, x, xs, unroll=cfg.unroll_scans)
    aux = jnp.sum(stats.pop("aux"))
    if dense_aux is not None:
        aux = aux + dense_aux
    reduce = {"moe_rows_held": jnp.mean, "moe_rows_buffered": jnp.mean,
              "moe_load_max_over_mean": jnp.max}
    return x, aux, {k: reduce[k](v) for k, v in stats.items()}


def _run_hybrid(cfg: ArchConfig, p: dict, x: jax.Array):
    """zamba2: groups of ``hybrid_every`` mamba2 layers, with ONE shared
    attention+MLP block (tied weights) applied between groups."""
    k = cfg.hybrid_every
    n_groups = cfg.n_layers // k
    grouped = jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups, k) + a.shape[1:]), p["layers"])
    shared = p["shared_attn"]

    def layer_body(x, lp):
        h, _, _ = L.mamba_block(lp["mamba"], _norm(cfg, lp["norm"], x),
                                state=cfg.ssm.state,
                                version=cfg.ssm.version,
                                headdim=cfg.ssm.headdim,
                                unroll_chunks=cfg.unroll_scans,
                                chunk=cfg.ssm_chunk)
        return x + h, jnp.zeros((), jnp.float32)

    def group_body(x, glp):
        x, auxs = jax.lax.scan(layer_body, x, glp,
                               unroll=cfg.unroll_scans)
        a, _ = L.attention_block(shared["attn"],
                                 _norm(cfg, shared["norm1"], x), cfg,
                                 causal=cfg.causal,
                                 window=cfg.sliding_window or None)
        x = x + a
        x = x + L.mlp_block(shared["mlp"],
                            _norm(cfg, shared["norm2"], x), cfg.act)
        return x, jnp.sum(auxs)

    fn = jax.checkpoint(group_body) if cfg.remat == "full" else group_body
    x, auxs = jax.lax.scan(fn, x, grouped, unroll=cfg.unroll_scans)
    return x, jnp.sum(auxs)


def _run_encoder(cfg: ArchConfig, p: dict, frames: jax.Array):
    def body(x, lp):
        a, _ = L.attention_block(lp["attn"], _norm(cfg, lp["norm1"], x),
                                 cfg, causal=False)
        x = x + a
        x = x + L.mlp_block(lp["mlp"], _norm(cfg, lp["norm2"], x), cfg.act)
        return x, None
    fn = jax.checkpoint(body) if cfg.remat == "full" else body
    x, _ = jax.lax.scan(fn, frames, p["enc_layers"])
    return x


def _logits(cfg: ArchConfig, p: dict, h: jax.Array) -> jax.Array:
    h = _norm(cfg, p["final_norm"], h)
    if cfg.tie_embeddings:
        return h @ p["embed"].T
    return h @ p["lm_head"]


# ---------------------------------------------------------------------------
# training / serving entry points
# ---------------------------------------------------------------------------

def train_loss(cfg: ArchConfig, p: dict, batch: dict) -> jax.Array:
    """batch: tokens (B,S) int32, labels (B,S) int32 (-1 = ignore);
    audio adds frames (B,enc_seq,D); vlm may add mrope_positions."""
    return train_loss_and_stats(cfg, p, batch)[0]


def train_loss_and_stats(cfg: ArchConfig, p: dict, batch: dict):
    """``train_loss`` and the held expert layers' counters of the step
    (``_run_decoder``; {} for a model without them)."""
    x = L.constrain(p["embed"][batch["tokens"]], "dp", "sp", None)
    enc_out = None
    if cfg.n_enc_layers:
        enc_out = _run_encoder(cfg, p, batch["frames"].astype(cfg.jdtype))
    h, aux, stats = _run_decoder(cfg, p, x, enc_out=enc_out,
                                 mrope_positions=batch.get("mrope_positions"))
    with jax.named_scope("lm_head_ce"):
        loss = _ce_loss(cfg, p, h, batch["labels"])
    return loss + 0.01 * aux, stats


def _ce_token_stats(cfg, p, h, labels):
    logits = _logits(cfg, p, h).astype(jnp.float32)
    # batch over dp, vocab over tp — without this constraint XLA has
    # been observed to replicate the vocab dim (tens of GB per device)
    logits = L.constrain(logits, "dp", None, "tp")
    valid = labels >= 0
    lbl = jnp.where(valid, labels, 0)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return nll.sum(), valid.sum()


def _ce_loss(cfg, p, h, labels):
    b, s, d = h.shape
    c = cfg.loss_chunk
    if not c or s % c or s == c:
        nll, nv = _ce_token_stats(cfg, p, h, labels)
        return nll / jnp.maximum(nv, 1)

    hc = h.reshape(b, s // c, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, s // c, c).transpose(1, 0, 2)

    def body(carry, xs):
        hi, li = xs
        nll, nv = _ce_token_stats(cfg, p, hi, li)
        return (carry[0] + nll, carry[1] + nv), None

    chunk_fn = jax.checkpoint(body) if cfg.remat == "full" else body
    (nll, nv), _ = jax.lax.scan(
        chunk_fn, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (hc, lc), unroll=cfg.unroll_scans)
    return nll / jnp.maximum(nv, 1)


def init_cache(cfg: ArchConfig, batch: int, max_seq: int) -> dict:
    dt = cfg.jdtype
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    cache: dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}
    if cfg.ssm and not cfg.hybrid_every:
        di = cfg.ssm.expand * cfg.d_model
        if cfg.ssm.version == 1:
            ssm_shape = (cfg.n_layers, batch, di, cfg.ssm.state)
        else:
            nh = di // cfg.ssm.headdim
            ssm_shape = (cfg.n_layers, batch, nh, cfg.ssm.headdim,
                         cfg.ssm.state)
        cache["conv"] = jnp.zeros((cfg.n_layers, batch,
                                   cfg.ssm.d_conv - 1, di), dt)
        cache["ssm"] = jnp.zeros(ssm_shape, jnp.float32)
    elif cfg.hybrid_every:
        di = cfg.ssm.expand * cfg.d_model
        nh = di // cfg.ssm.headdim
        n_groups = cfg.n_layers // cfg.hybrid_every
        win = cfg.sliding_window or max_seq
        win = min(win, max_seq)
        cache["conv"] = jnp.zeros((cfg.n_layers, batch,
                                   cfg.ssm.d_conv - 1, di), dt)
        cache["ssm"] = jnp.zeros((cfg.n_layers, batch, nh,
                                  cfg.ssm.headdim, cfg.ssm.state),
                                 jnp.float32)
        cache["k"] = jnp.zeros((n_groups, batch, hkv, win, hd), dt)
        cache["v"] = jnp.zeros((n_groups, batch, hkv, win, hd), dt)
    elif cfg.kv_lora_rank:
        # the expanded per-head keys and values, not the latent
        cache["k"] = jnp.zeros((cfg.n_layers, batch, cfg.n_heads, max_seq,
                                cfg.qk_nope_head_dim + cfg.qk_rope_head_dim),
                               dt)
        cache["v"] = jnp.zeros((cfg.n_layers, batch, cfg.n_heads, max_seq,
                                cfg.v_head_dim), dt)
    else:
        cache["k"] = jnp.zeros((cfg.n_layers, batch, hkv, max_seq, hd), dt)
        cache["v"] = jnp.zeros((cfg.n_layers, batch, hkv, max_seq, hd), dt)
        if cfg.n_enc_layers:
            cache["cross_k"] = jnp.zeros(
                (cfg.n_layers, batch, hkv, cfg.enc_seq, hd), dt)
            cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
    return cache


def prefill(cfg: ArchConfig, p: dict, batch: dict, max_seq: int):
    """Run the full prompt, return (last-token logits, filled cache).
    Uses the training forward (no incremental cache fill) then a cache
    built from the same projections — for the dry-run we prefill by
    running the chunked forward and materializing caches layerwise."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = p["embed"][tokens]
    cache = init_cache(cfg, b, max_seq)
    enc_out = None
    if cfg.n_enc_layers:
        enc_out = _run_encoder(cfg, p, batch["frames"].astype(cfg.jdtype))
        cache["enc_out"] = enc_out
    # for shapes/roofline purposes prefill = decoder forward; cache fill
    # is a cheap scatter of the per-layer K/V (done inside attention on
    # the serving path; here we run the stack and return hidden states)
    h, _, _ = _run_decoder(cfg, p, x, enc_out=enc_out,
                           mrope_positions=batch.get("mrope_positions"))
    logits = _logits(cfg, p, h[:, -1:, :])
    cache["len"] = jnp.full((), s, jnp.int32)
    return logits, cache


def decode_step(cfg: ArchConfig, p: dict, token: jax.Array, cache: dict):
    """One decode step. token: (B, 1) int32.  Returns (logits, cache)."""
    x = p["embed"][token]                              # (B,1,D)
    pos = cache["len"]

    if cfg.ssm and not cfg.hybrid_every:
        def body(x, xs):
            lp, conv, ssm = xs
            h, new_conv, new_ssm = L.mamba_block(
                lp["mamba"], _norm(cfg, lp["norm"], x),
                state=cfg.ssm.state, version=cfg.ssm.version,
                conv_state=conv, ssm_state=ssm, headdim=cfg.ssm.headdim)
            return x + h, (new_conv, new_ssm)
        x, (conv, ssm) = jax.lax.scan(
            body, x, (p["layers"], cache["conv"], cache["ssm"]),
            unroll=cfg.unroll_scans)
        cache = dict(cache, conv=conv, ssm=ssm,
                     len=cache["len"] + 1)
    elif cfg.hybrid_every:
        k = cfg.hybrid_every
        n_groups = cfg.n_layers // k
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape((n_groups, k) + a.shape[1:]), p["layers"])
        gconv = cache["conv"].reshape((n_groups, k)
                                      + cache["conv"].shape[1:])
        gssm = cache["ssm"].reshape((n_groups, k) + cache["ssm"].shape[1:])
        shared = p["shared_attn"]
        win = cache["k"].shape[3]
        # sliding-window cache position
        wpos = jnp.minimum(pos, win - 1)

        def group_body(x, xs):
            glp, conv_g, ssm_g, kc, vc = xs

            def layer_body(x, ys):
                lp, conv, ssm = ys
                h, nc, ns = L.mamba_block(
                    lp["mamba"], _norm(cfg, lp["norm"], x),
                    state=cfg.ssm.state, version=cfg.ssm.version,
                    conv_state=conv, ssm_state=ssm,
                    headdim=cfg.ssm.headdim)
                return x + h, (nc, ns)
            x, (nconv, nssm) = jax.lax.scan(layer_body, x,
                                            (glp, conv_g, ssm_g))
            a, (nk, nv) = L.attention_block(
                shared["attn"], _norm(cfg, shared["norm1"], x), cfg,
                kv_cache=(kc, vc), cache_len=wpos,
                window=cfg.sliding_window or None)
            x = x + a
            x = x + L.mlp_block(shared["mlp"],
                                _norm(cfg, shared["norm2"], x), cfg.act)
            return x, (nconv, nssm, nk, nv)

        x, (conv, ssm, kc, vc) = jax.lax.scan(
            group_body, x, (grouped, gconv, gssm, cache["k"], cache["v"]),
            unroll=cfg.unroll_scans)
        cache = dict(cache,
                     conv=conv.reshape(cache["conv"].shape),
                     ssm=ssm.reshape(cache["ssm"].shape),
                     k=kc, v=vc, len=cache["len"] + 1)
    else:
        have_cross = "cross_layers" in p

        def body(x, xs):
            if have_cross:
                lp, clp, kc, vc, ck, cv = xs
            else:
                lp, kc, vc = xs
            a, (nk, nv) = L.attention_block(
                lp["attn"], _norm(cfg, lp["norm1"], x), cfg,
                kv_cache=(kc, vc), cache_len=pos)
            x = x + a
            if have_cross:
                x = x + _cross_cached(cfg, clp, x, ck, cv)
            h = _norm(cfg, lp["norm2"], x)
            if "moe" in lp:
                m, _ = _moe_dispatch(cfg, lp["moe"], h)
                x = x + m
            else:
                x = x + L.mlp_block(lp["mlp"], h, cfg.act)
            if have_cross:
                return x, (nk, nv, ck, cv)
            return x, (nk, nv)

        if have_cross:
            xs = (p["layers"], p["cross_layers"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"])
            x, (kc, vc, ck, cv) = jax.lax.scan(body, x, xs,
                                               unroll=cfg.unroll_scans)
            cache = dict(cache, k=kc, v=vc, len=cache["len"] + 1)
        else:
            # leading dense layers hold the first entries of the cache
            nd = cfg.n_dense_layers if "dense_layers" in p else 0
            kv = []
            for lps, lo, hi in ((p.get("dense_layers"), 0, nd),
                                (p["layers"], nd, cfg.n_layers)):
                if hi > lo:
                    x, kv_part = jax.lax.scan(
                        body, x, (lps, cache["k"][lo:hi], cache["v"][lo:hi]),
                        unroll=cfg.unroll_scans)
                    kv.append(kv_part)
            kc, vc = (jnp.concatenate(parts) if len(parts) > 1 else parts[0]
                      for parts in zip(*kv))
            cache = dict(cache, k=kc, v=vc, len=cache["len"] + 1)

    return _logits(cfg, p, x), cache


def _cross_cached(cfg, clp, x, ck, cv):
    from .attention import decode_attention
    b, s, _ = x.shape
    hq, hd = cfg.n_heads, cfg.head_dim
    ap = clp["attn"]
    q = (_norm(cfg, clp["norm"], x) @ ap["wq"]).reshape(
        b, s, hq, hd).transpose(0, 2, 1, 3)
    o = decode_attention(q, ck, cv, ck.shape[2])
    return o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd) @ ap["wo"]
