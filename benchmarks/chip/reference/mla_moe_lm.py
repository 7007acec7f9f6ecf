"""Plain reference of a DeepSeek-V3-style decoder's training steps, on
one chip's share of its experts: multi-head latent attention, leading
dense layers, then expert layers.

Straightforward ``jax.numpy`` in float32 at the ``highest`` matmul
precision; imports nothing of the program (the float8 control, the
norm, rope, the schedule and the state layout are ``dense_lm``'s).  It
follows the published architecture:

- attention (MLA, no query compression): q = x·W_q, heads of
  ``qk_nope_head_dim`` + ``qk_rope_head_dim``; x·W_kva gives the latent
  c (``kv_lora_rank``) and one rope key that every head shares; RMSNorm
  of c, times W_kvb, gives each head's nope key and its value
  (``v_head_dim``); rope turns the rope dims alone; causal softmax of
  q·k / (nope + rope)^1/2;
- ``first_k_dense_replace`` dense SwiGLU layers of ``intermediate_size``;
- expert layers: s = sigmoid(x·W_r) over all the published experts, the
  top ``num_experts_per_tok`` of s + b chosen (b the correction bias,
  which takes no gradient), weighted by s normalised over them and
  scaled by ``routed_scaling_factor``; the experts this chip holds
  (``n_routed_experts`` of them from ``first_held_expert``) add their
  weighted SwiGLU of ``moe_intermediate_size``, each over every token
  with the weight 0 where the token did not choose it; the shared
  experts (one SwiGLU ``n_shared_experts`` times as wide) add theirs;
- an untied LM head over the vocabulary slice, mean next-token
  cross-entropy.

It runs in blocks so that it fits on the chip: layers under
``jax.checkpoint``, attention a block of queries of one (row, head) at
a time, the MLPs and
the experts a block of tokens at a time, the LM head and loss a block of
rows at a time.
``precision="fp8"`` is ``dense_lm``'s control: every matmul, the
router's too, takes its operands through float8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.dense_lm import init_state, lr_at, make_mm, rmsnorm, rope

ROW_BLOCK = 512    # rows of the LM head and loss per block
TOKEN_BLOCK = 2048  # tokens of the MLPs and experts per block
QUERY_BLOCK = 2048  # queries of attention per block


def attention(mm, q, k, v):
    """Causal softmax attention, one (row, head) and ``QUERY_BLOCK``
    queries at a time.  q, k (B, H, S, D), v (B, H, S, Dv) -> (B, H, S,
    Dv)."""
    b, h, s, d = q.shape
    dv = v.shape[-1]
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def head(qkv):
        qi, ki, vi = qkv

        @jax.checkpoint
        def block(args):
            qj, first = args
            mask = (first + jnp.arange(qb))[:, None] >= jnp.arange(s)[None]
            sc = mm("qd,kd->qk", qj, ki) * d ** -0.5
            sc = jnp.where(mask, sc, -jnp.inf)
            return mm("qk,kd->qd", jax.nn.softmax(sc, -1), vi)

        out = jax.lax.map(block, (qi.reshape(s // qb, qb, d),
                                  jnp.arange(0, s, qb)))
        return out.reshape(s, dv)

    out = jax.lax.map(head, (q.reshape(b * h, s, d), k.reshape(b * h, s, d),
                             v.reshape(b * h, s, dv)))
    return out.reshape(b, h, s, dv)


def mla(mm, c, x, ap):
    b, s, _ = x.shape
    h = c["num_attention_heads"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    dv, r = c["v_head_dim"], c["kv_lora_rank"]
    theta, layout = c["rope_theta"], c["as_run"]["rope_layout"]
    q = mm("bsd,de->bse", x, ap["wq"]).reshape(b, s, h, dn + dr)
    q = q.transpose(0, 2, 1, 3)
    kva = mm("bsd,de->bse", x, ap["wkv_a"])
    latent = rmsnorm(kva[..., :r], ap["kv_norm"], c["rms_norm_eps"])
    kv = mm("bsr,re->bse", latent, ap["wkv_b"]).reshape(b, s, h, dn + dv)
    kv = kv.transpose(0, 2, 1, 3)
    k_rope = rope(kva[:, None, :, r:], theta, layout)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta, layout)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (b, h, s, dr))], -1)
    o = attention(mm, q, k, kv[..., dn:])
    return mm("bse,ed->bsd", o.transpose(0, 2, 1, 3).reshape(b, s, h * dv),
              ap["wo"])


def swiglu(mm, x, w_gate, w_up, w_down):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, w_gate))
              * mm("td,df->tf", x, w_up), w_down)


def in_blocks(fn, *rows):
    """``fn`` over ``TOKEN_BLOCK`` rows of each of ``rows`` at a time,
    each block under ``jax.checkpoint``; the results stacked again."""
    n = rows[0].shape[0]
    tb = TOKEN_BLOCK if n % TOKEN_BLOCK == 0 else n
    out = jax.lax.map(jax.checkpoint(lambda r: fn(*r)),
                      tuple(x.reshape(n // tb, tb, *x.shape[1:])
                            for x in rows))
    return out.reshape(n, *out.shape[2:])


def gates(mm, c, xt, mp):
    """(T, E) routing weights over all the published experts, zero where
    a token did not choose the expert."""
    k = c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(mm("td,de->te", xt, mp["router"]))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(mp["bias"]), k)
    w = jnp.take_along_axis(scores, idx, -1)
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * c["routed_scaling_factor"]
    rows = jnp.arange(xt.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(w)


def moe(mm, c, xt, mp):
    """The held experts' weighted outputs plus the shared experts'."""
    lo = c["first_held_expert"]
    g = gates(mm, c, xt, mp)[:, lo:lo + c["n_routed_experts"]]
    sp = mp["shared"]

    def rows(x, ge):
        y = swiglu(mm, x, sp["w_gate"], sp["w_up"], sp["w_down"])
        for e in range(ge.shape[-1]):
            y = y + swiglu(mm, x, mp["we_gate"][e], mp["we_up"][e],
                           mp["we_down"][e]) * ge[:, e:e + 1]
        return y
    return in_blocks(rows, xt, g)


def layer(mm, c, x, lp):
    eps = c["rms_norm_eps"]
    b, s, d = x.shape
    x = x + mla(mm, c, rmsnorm(x, lp["norm1"], eps), lp["attn"])
    h = rmsnorm(x, lp["norm2"], eps).reshape(b * s, d)
    if "moe" in lp:
        out = moe(mm, c, h, lp["moe"])
    else:
        mp = lp["mlp"]
        out = in_blocks(lambda r: swiglu(mm, r, mp["w_gate"], mp["w_up"],
                                         mp["w_down"]), h)
    return x + out.reshape(b, s, d)


def loss_fn(mm, c, p, tokens, labels):
    """Mean next-token cross-entropy over every position."""
    x = p["embed"][tokens]
    body = jax.checkpoint(lambda x, lp: (layer(mm, c, x, lp), None))
    x, _ = jax.lax.scan(body, x, p["dense_layers"])
    x, _ = jax.lax.scan(body, x, p["layers"])
    h = rmsnorm(x, p["final_norm"], c["rms_norm_eps"])
    h = h.reshape(-1, h.shape[-1])
    lab = labels.reshape(-1)
    n = h.shape[0]
    rb = ROW_BLOCK if n % ROW_BLOCK == 0 else n

    @jax.checkpoint
    def rows(hl):
        hr, lr = hl
        logits = mm("rd,dv->rv", hr, p["lm_head"])
        gold = jnp.take_along_axis(logits, lr[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    nll = jax.lax.map(rows, (h.reshape(n // rb, rb, -1),
                             lab.reshape(n // rb, rb)))
    return jnp.sum(nll) / n


def make_step(c: dict, precision: str = "f32"):
    """``step(state, batch) -> (state, {"loss", "gnorm"})``, AdamW with
    global-norm clipping and decoupled weight decay on every leaf, the
    program's state layout (``dense_lm.init_state``).  The gradient and
    the update are two programs (``step.grads``, ``step.update``, the
    second donating the state), and AdamW's moments wait in host memory
    while the first runs: the float32 copy of the parameters, the
    gradient and the layers' residuals do not fit on the chip beside
    them."""
    mm = make_mm(precision)
    opt = c["optimizer"]
    b1, b2 = opt["b1"], opt["b2"]

    @jax.jit
    def grads(params, batch):
        p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        return jax.value_and_grad(
            lambda p: loss_fn(mm, c, p, batch["tokens"], batch["labels"])
        )(p32)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def update(state, g):
        gnorm = jnp.sqrt(sum(jnp.sum(x * x)
                             for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        t = state["opt"]["step"] + 1
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)
        lr = lr_at(opt, state["opt"]["step"])

        def upd(p_old, g, m, v):
            p = p_old.astype(jnp.float32)
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            new = p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
                            + opt["weight_decay"] * p)
            return new.astype(p_old.dtype), m, v

        out = jax.tree_util.tree_map(upd, state["params"], g,
                                     state["opt"]["m"], state["opt"]["v"])
        pick = lambda i: jax.tree_util.tree_map(   # noqa: E731
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return ({"params": pick(0),
                 "opt": {"m": pick(1), "v": pick(2), "step": t},
                 "step": state["step"] + 1}, gnorm)

    def step(state, batch):
        # the moments wait on the host while the gradient is computed
        moments = jax.device_get((state["opt"]["m"], state["opt"]["v"]))
        for leaf in jax.tree_util.tree_leaves((state["opt"]["m"],
                                               state["opt"]["v"])):
            leaf.delete()
        loss, g = grads(state["params"], batch)
        m, v = jax.device_put(moments)
        state = dict(state, opt=dict(state["opt"], m=m, v=v))
        state, gnorm = update(state, g)
        return state, {"loss": loss, "gnorm": gnorm}

    step.grads, step.update = grads, update
    return step


def param_avals(c: dict) -> dict:
    """The parameter tree's shapes and dtypes as the configuration states
    them, each stack of layers on a leading axis; the router and its
    bias in float32."""
    dt = jnp.dtype(c["torch_dtype"])
    f32 = jnp.dtype(jnp.float32)
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    dv, r = c["v_head_dim"], c["kv_lora_rank"]
    nd = c["first_k_dense_replace"]
    nm = c["num_hidden_layers"] - nd
    held, fe = c["n_routed_experts"], c["moe_intermediate_size"]
    fs = fe * c["n_shared_experts"]
    router = c["published"]["n_routed_experts"]
    vocab, ff = c["vocab_size"], c["intermediate_size"]

    def sd(*s, dtype=dt):
        return jax.ShapeDtypeStruct(s, dtype)

    def block(n):
        return {"norm1": sd(n, d), "norm2": sd(n, d),
                "attn": {"wq": sd(n, d, h * (dn + dr)),
                         "wkv_a": sd(n, d, r + dr), "kv_norm": sd(n, r),
                         "wkv_b": sd(n, r, h * (dn + dv)),
                         "wo": sd(n, h * dv, d)}}

    def swiglu_avals(*lead, f):
        return {"w_gate": sd(*lead, d, f), "w_up": sd(*lead, d, f),
                "w_down": sd(*lead, f, d)}

    dense = dict(block(nd), mlp=swiglu_avals(nd, f=ff))
    experts = {"we_" + k[2:]: v
               for k, v in swiglu_avals(nm, held, f=fe).items()}
    layers = dict(block(nm), moe=dict(
        experts, router=sd(nm, d, router, dtype=f32),
        bias=sd(nm, router, dtype=f32), shared=swiglu_avals(nm, f=fs)))
    return {"embed": sd(vocab, d), "final_norm": sd(d),
            "lm_head": sd(d, vocab), "dense_layers": dense,
            "layers": layers}


__all__ = ["init_state", "make_step", "param_avals"]
