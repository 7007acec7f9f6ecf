"""Plain reference of a dense decoder's training steps.

Straightforward ``jax.numpy`` in float32 at the ``highest`` matmul
precision, imports nothing of the program.  It follows the published
architecture (pre-norm RMSNorm, rotary attention with optional QKV bias,
SwiGLU MLP, tied embedding and LM head, mean next-token cross-entropy)
and the configuration's ``as_run`` facts where the program departs from
the publication (RMSNorm's eps, the rotary layout, MiniCPM's scalars);
``PERF.md`` lists those.  The optimizer is AdamW with global-norm
clipping, float32 moments and parameters kept in the configuration's
dtype, as the configuration states.

It runs in blocks so that it fits beside nothing else on the chip:
layers under ``jax.checkpoint``, attention a few heads at a time, the
LM head and loss a block of rows at a time.

``precision="fp8"`` is the control: every matmul takes its operands
through float8 (e4m3 forward, e5m2 for the cotangents, each tensor
scaled to its own absolute maximum) and accumulates in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
HEAD_GROUP = 4     # attention heads per block
ROW_BLOCK = 512    # rows of the LM head and loss per block


# --------------------------------------------------------------------------
# float8 control
# --------------------------------------------------------------------------

def _fp8(x, dtype, fmax):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, fmax / amax, 1.0)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def q8(x):
    return _fp8(x, jnp.float8_e4m3fn, 448.0)


def _q8_fwd(x):
    return q8(x), None


def _q8_bwd(_, ct):
    return (_fp8(ct, jnp.float8_e5m2, 57344.0),)


q8.defvjp(_q8_fwd, _q8_bwd)


def make_mm(precision: str):
    if precision == "f32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda eq, a, b: jnp.einsum(eq, q8(a), q8(b),
                                           precision=HIGHEST)
    raise ValueError(f"unknown reference precision {precision!r}")


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta, layout):
    """x: (B, H, S, D)."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if layout == "interleaved":
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(mm, q, k, v):
    """Causal softmax attention, ``HEAD_GROUP`` heads at a time.
    q, k, v: (B, H, S, D)."""
    b, h, s, d = q.shape
    g = HEAD_GROUP if h % HEAD_GROUP == 0 else 1
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def group(qkv):
        qg, kg, vg = qkv
        sc = mm("bhqd,bhkd->bhqk", qg, kg) * d ** -0.5
        sc = jnp.where(mask, sc, -jnp.inf)
        return mm("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), vg)

    def split(t):
        return jnp.moveaxis(t.reshape(b, h // g, g, s, d), 1, 0)

    out = jax.lax.map(group, (split(q), split(k), split(v)))
    return jnp.moveaxis(out, 0, 1).reshape(b, h, s, d)


def layer(mm, c, x, lp):
    run = c["as_run"]
    b, s, _ = x.shape
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["head_dim"]
    ap = lp["attn"]
    h = rmsnorm(x, lp["norm1"], run["rms_norm_eps"])
    q = mm("bsd,de->bse", h, ap["wq"])
    k = mm("bsd,de->bse", h, ap["wk"])
    v = mm("bsd,de->bse", h, ap["wv"])
    if "bq" in ap:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    q = q.reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    q = rope(q, c["rope_theta"], run["rope_layout"])
    k = rope(k, c["rope_theta"], run["rope_layout"])
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    o = attention(mm, q, k, v).transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    x = x + run["residual_scale"] * mm("bse,ed->bsd", o, ap["wo"])
    mp = lp["mlp"]
    h = rmsnorm(x, lp["norm2"], run["rms_norm_eps"])
    gate = jax.nn.silu(mm("bsd,df->bsf", h, mp["w_gate"]))
    up = mm("bsd,df->bsf", h, mp["w_up"])
    return x + run["residual_scale"] * mm("bsf,fd->bsd", gate * up,
                                          mp["w_down"])


def loss_fn(mm, c, p, tokens, labels):
    """Mean next-token cross-entropy over every position."""
    run = c["as_run"]
    x = p["embed"][tokens] * run["embed_scale"]
    body = jax.checkpoint(lambda x, lp: (layer(mm, c, x, lp), None))
    x, _ = jax.lax.scan(body, x, p["layers"])
    h = rmsnorm(x, p["final_norm"], run["rms_norm_eps"])
    h = h.reshape(-1, h.shape[-1])
    lab = labels.reshape(-1)
    n = h.shape[0]
    rb = ROW_BLOCK if n % ROW_BLOCK == 0 else n

    @jax.checkpoint
    def rows(hl):
        hr, lr = hl
        logits = mm("rd,vd->rv", hr, p["embed"]) * run["logit_scale"]
        gold = jnp.take_along_axis(logits, lr[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    nll = jax.lax.map(rows, (h.reshape(n // rb, rb, -1),
                             lab.reshape(n // rb, rb)))
    return jnp.sum(nll) / n


# --------------------------------------------------------------------------
# optimizer and the training step
# --------------------------------------------------------------------------

def lr_at(opt: dict, step):
    """The configuration's schedule at 0-based optimizer step ``step``."""
    peak, total = opt["peak_lr"], opt["horizon_steps"]
    step = jnp.asarray(step, jnp.float32)
    warm = max(1, int(total * 0.01))
    if opt["schedule"] == "cosine":
        t = jnp.clip((step - warm) / max(1, total - warm), 0.0, 1.0)
        later = peak * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    elif opt["schedule"] == "wsd":
        start = int(total * 0.9)
        t = jnp.clip((step - start) / max(1, total - start), 0.0, 1.0)
        later = jnp.where(step < start, peak,
                          peak * jnp.exp(jnp.log(0.1) * t))
    else:
        raise ValueError(f"unknown schedule {opt['schedule']!r}")
    return jnp.where(step < warm, peak * step / warm, later)


def make_step(c: dict, precision: str = "f32"):
    """``step(state, batch) -> (state, {"loss", "gnorm"})`` with the
    program's state layout: ``{"params", "opt": {"m", "v", "step"},
    "step"}``."""
    mm = make_mm(precision)
    opt = c["optimizer"]
    b1, b2 = opt["b1"], opt["b2"]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, batch):
        p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                     state["params"])
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(mm, c, p, batch["tokens"], batch["labels"])
        )(p32)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x)
                             for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        t = state["opt"]["step"] + 1
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)
        lr = lr_at(opt, state["opt"]["step"])

        def upd(p, p_old, g, m, v):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            new = p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
                            + opt["weight_decay"] * p)
            return new.astype(p_old.dtype), m, v

        out = jax.tree_util.tree_map(upd, p32, state["params"], g,
                                     state["opt"]["m"], state["opt"]["v"])
        pick = lambda i: jax.tree_util.tree_map(   # noqa: E731
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return ({"params": pick(0),
                 "opt": {"m": pick(1), "v": pick(2), "step": t},
                 "step": state["step"] + 1},
                {"loss": loss, "gnorm": gnorm})

    return step


def init_state(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)   # noqa: E731
    return {"params": params,
            "opt": {"m": jax.tree_util.tree_map(zeros, params),
                    "v": jax.tree_util.tree_map(zeros, params),
                    "step": jnp.zeros((), jnp.int32)},
            "step": jnp.zeros((), jnp.int32)}


def param_avals(c: dict) -> dict:
    """The parameter tree's shapes and dtype as the configuration states
    them, layers stacked on a leading axis."""
    dt = jnp.dtype(c["torch_dtype"])
    d, ff, n = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    sd = lambda *s: jax.ShapeDtypeStruct(s, dt)   # noqa: E731
    attn = {"wq": sd(n, d, hq * hd), "wk": sd(n, d, hkv * hd),
            "wv": sd(n, d, hkv * hd), "wo": sd(n, hq * hd, d)}
    if c["qkv_bias"]:
        attn.update(bq=sd(n, hq * hd), bk=sd(n, hkv * hd), bv=sd(n, hkv * hd))
    return {"embed": sd(c["vocab_size"], d), "final_norm": sd(d),
            "layers": {"norm1": sd(n, d), "attn": attn, "norm2": sd(n, d),
                       "mlp": {"w_up": sd(n, d, ff), "w_down": sd(n, ff, d),
                               "w_gate": sd(n, d, ff)}}}
