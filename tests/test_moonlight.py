"""Moonlight-16B-A3B's mechanisms against the benchmark's plain reference
(``benchmarks/chip/reference/mla_moe_lm.py``), at a small size on the
CPU, on seeded random weights: latent attention (MLA), the dropless
expert layer over the experts a chip holds, the shares of a layer
summing to the whole, a whole training step, and the grouped matmul's
backward.  The program runs in float32 here, so each gap is one of
summation order alone.
"""
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config
from repro.kernels.train_gmm import grouped_matmul
from repro.models import layers as L

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

from reference import dense_lm, mla_moe_lm  # noqa: E402

F32 = jnp.float32
CELL_CONFIG = CHIP / "configs" / "moonlight-16b-a3b-6l.json"
MM = dense_lm.make_mm("f32")
# the reference at the highest precision against the program's float32
# on the CPU: both exact up to summation order, so a relative gap of
# 1e-5 on activations of order 1 is rounding, and anything a mechanism
# got wrong (a rope dim, a scale, a dropped row) is of order 1
RTOL = 1e-5


def small_config() -> dict:
    """The cell's configuration file at a small size: 16 experts routed,
    4 held from the 5th, top-6 of them."""
    c = json.loads(CELL_CONFIG.read_text())
    c.update(hidden_size=64, intermediate_size=96, num_hidden_layers=3,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             moe_intermediate_size=32, n_routed_experts=4, vocab_size=128,
             first_held_expert=4, torch_dtype="float32")
    c["published"] = dict(c["published"], n_routed_experts=16)
    c["program"] = dict(
        c["program"], n_layers=3, vocab=128, d_model=64, d_ff=96,
        n_heads=4, n_kv_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, dtype="float32",
        moe=dict(c["program"]["moe"], n_experts=16, d_expert=32, n_held=4,
                 held_offset=4))
    return c


def program_config(c: dict):
    return dataclasses.replace(get_config(c["arch"]), **c["program"])


def _normal(key, shape, scale):
    return jax.random.normal(jax.random.PRNGKey(key), shape, F32) * scale


def test_published_preset():
    cfg = get_config("moonlight-16b-a3b")
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.d_ff, cfg.vocab) == (
        27, 1, 11264, 163840)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.rms_norm_eps) == (512, 128, 64, 128, 1e-5)
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.n_shared,
            cfg.moe.d_expert, cfg.moe.router, cfg.moe.routed_scaling,
            cfg.moe.n_held) == (64, 6, 2, 1408, "sigmoid", 2.446, 0)


def test_cell_config_builds_the_program_config():
    # the lane builds the program's config from the file's ``program``,
    # a JSON dict for ``moe`` among it, and checks ``program_keys``
    c = json.loads(CELL_CONFIG.read_text())
    cfg = program_config(c)
    assert cfg.moe.n_held == c["n_routed_experts"] == 8
    assert cfg.moe.n_experts == c["published"]["n_routed_experts"] == 64
    for field, key in c["program_keys"].items():
        assert getattr(cfg, field) == c[key], field


def test_mla_matches_reference():
    c = small_config()
    cfg = program_config(c)
    ap = L.init_mla(jax.random.PRNGKey(0), 64, 4, 32, 16, 8, 16, F32)
    ap["kv_norm"] = 1 + _normal(1, (32,), 0.1)
    x = _normal(2, (2, 128, 64), 1.0)
    got, _ = L.mla_block(ap, x, cfg)
    want = mla_moe_lm.mla(MM, c, x, ap)
    np.testing.assert_allclose(got, want, rtol=RTOL * 10, atol=RTOL)


def _moe_params(c, n_experts, held, seed=0):
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    p = L.init_moe(jax.random.PRNGKey(seed), d, f, n_experts,
                   c["n_shared_experts"], "swiglu", F32, n_held=held,
                   router_bias=True)
    p["bias"] = _normal(seed + 1, (n_experts,), 0.05)
    return p


def _held_block(c, p, x, **kw):
    e = program_config(c).moe
    args = dict(n_experts=e.n_experts, top_k=e.top_k, n_held=e.n_held,
                held_offset=e.held_offset, router="sigmoid",
                routed_scaling=e.routed_scaling)
    return L.moe_block_held(p, x, **dict(args, **kw))


def test_held_experts_match_reference_under_imbalance():
    # one held expert takes every token (its bias +1 outweighs any
    # score), the other three some; nothing is dropped
    c = small_config()
    p = _moe_params(c, 16, 4)
    p["bias"] = p["bias"].at[5].set(1.0)
    x = _normal(3, (2, 64, 64), 1.0)
    y, stats = _held_block(c, p, x)
    xt = x.reshape(-1, 64)
    want = mla_moe_lm.moe(MM, c, xt, p)
    np.testing.assert_allclose(y.reshape(-1, 64), want, rtol=RTOL * 10,
                               atol=RTOL)
    chosen = mla_moe_lm.gates(MM, c, xt, p)[:, 4:8] > 0
    counts = np.asarray(chosen.sum(0))
    assert counts[1] == xt.shape[0]
    assert float(stats["moe_rows_held"]) == counts.sum()
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(
        counts.max() / counts.mean())
    assert float(stats["aux"]) == 0.0


def test_row_ladder_of_the_cell():
    # Moonlight's cell: 2 x 8192 tokens, top-6, 8 of 64 experts held;
    # 12288 rows expected, so the buffer starts at twice that
    assert L.row_ladder(16384, 6, 8, 64) == (24576, 49152, 98304)
    # every expert held: the worst case is twice the expected count or
    # less, so one size and no switch
    assert L.row_ladder(16384, 6, 64, 64) == (16384 * 6,)


@pytest.mark.parametrize("t,k,held,n_experts", [
    (16384, 6, 8, 64), (16384, 6, 64, 64), (4096, 2, 1, 8),
    (1024, 6, 4, 64), (1000, 8, 3, 160), (128, 6, 4, 16)])
def test_row_ladder_ends_at_the_worst_case(t, k, held, n_experts):
    ladder = L.row_ladder(t, k, held, n_experts)
    assert ladder[-1] == t * min(k, held)
    assert list(ladder) == sorted(set(ladder))
    assert all(r % 512 == 0 for r in ladder[:-1])


# (bias added to every held expert's, bias added to the first held
# expert's, the rows each rung of the ladder may hold): 1024 tokens
# choose 6 of 64 experts, 4 held, so the ladder is (1024, 2048, 4096)
RUNGS = {
    # ~384 held rows: the smallest rung
    "smallest": (0.0, 0.0, 1024),
    # the first held expert takes every token, the others ~96 each
    "overflow": (0.0, 1.0, 2048),
    # every held expert takes every token: the worst case
    "worst": (1.0, 0.0, 4096),
}


@pytest.mark.parametrize("case", sorted(RUNGS))
def test_held_layer_rungs_match_reference(case):
    # forward and gradient against the reference, on whichever rung the
    # step's count of held rows lands; moe_rows_buffered is that rung
    every, first, rung = RUNGS[case]
    c = small_config()
    p = _moe_params(c, 64, 4, seed=9)
    p["bias"] = p["bias"].at[4:8].add(every).at[4].add(first)
    x = _normal(10, (2, 512, 64), 1.0)
    ct = _normal(11, (1024, 64), 1.0)
    assert L.row_ladder(1024, 6, 4, 64) == (1024, 2048, 4096)

    def program(x, p):
        y, stats = _held_block(c, p, x, n_experts=64)
        return jnp.sum(y.reshape(-1, 64) * ct), stats

    def reference(x, p):
        return jnp.sum(mla_moe_lm.moe(MM, c, x.reshape(-1, 64), p) * ct)

    (got, stats), (gx, gp) = jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True)(x, p)
    want, (wx, wp) = jax.value_and_grad(reference, argnums=(0, 1))(x, p)
    held = int(stats["moe_rows_held"])
    assert float(stats["moe_rows_buffered"]) == rung
    assert held <= rung and (rung == 1024 or held > rung // 2)
    np.testing.assert_allclose(got, want, rtol=RTOL * 10)
    np.testing.assert_allclose(gx, wx, rtol=RTOL * 10, atol=RTOL * 10)
    for name in ("we_gate", "we_up", "we_down"):
        np.testing.assert_allclose(gp[name], wp[name], rtol=RTOL * 10,
                                   atol=RTOL * 10, err_msg=name)


def test_every_expert_held_runs_one_rung():
    # n_held 0: all 16 experts here, one buffer of every (token, k) pair
    c = dict(small_config(), n_routed_experts=16, first_held_expert=0)
    p = _moe_params(c, 16, 0, seed=12)
    x = _normal(13, (2, 64, 64), 1.0)
    ct = _normal(14, (128, 64), 1.0)
    assert L.row_ladder(128, 6, 16, 16) == (768,)

    def program(x, p):
        y, stats = _held_block(c, p, x, n_held=0, held_offset=0)
        return jnp.sum(y.reshape(-1, 64) * ct), stats

    def reference(x, p):
        return jnp.sum(mla_moe_lm.moe(MM, c, x.reshape(-1, 64), p) * ct)

    (got, stats), (gx, gp) = jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True)(x, p)
    want, (wx, wp) = jax.value_and_grad(reference, argnums=(0, 1))(x, p)
    assert float(stats["moe_rows_held"]) == 128 * 6
    assert float(stats["moe_rows_buffered"]) == 128 * 6
    np.testing.assert_allclose(got, want, rtol=RTOL * 10)
    np.testing.assert_allclose(gx, wx, rtol=RTOL * 10, atol=RTOL * 10)
    for name in ("we_gate", "we_up", "we_down"):
        np.testing.assert_allclose(gp[name], wp[name], rtol=RTOL * 10,
                                   atol=RTOL * 10, err_msg=name)


def test_shares_sum_to_the_whole_layer():
    # 8 chips of an EP8 layer, 2 of 16 experts each: their routed parts,
    # with the shared experts (which every chip computes) counted once,
    # are the uncut layer
    c = small_config()
    whole = _moe_params(c, 16, 16, seed=4)
    x = _normal(5, (2, 32, 64), 1.0)
    xt = x.reshape(-1, 64)
    shared = L.mlp_block(whole["shared"], xt, "swiglu")
    total = shared
    for chip in range(8):
        part = dict(whole, **{k: whole[k][2 * chip:2 * chip + 2]
                              for k in ("we_gate", "we_up", "we_down")})
        y, _ = _held_block(c, part, x, n_held=2, held_offset=2 * chip)
        total = total + y.reshape(-1, 64) - shared
    uncut = dict(c, n_routed_experts=16, first_held_expert=0)
    want = mla_moe_lm.moe(MM, uncut, xt, whole)
    np.testing.assert_allclose(total, want, rtol=RTOL * 10, atol=RTOL * 10)


@pytest.mark.parametrize("fused", [False, True])
def test_grouped_matmul_vjp_matches_einsum(fused):
    # experts 1 and 2 of 4 held; rows sorted by expert; the megablox
    # kernels run interpreted
    sizes = jnp.array([24, 40, 16, 48], jnp.int32)
    m, k, n = 128, 128, 256
    gid = jnp.repeat(jnp.arange(4), sizes, total_repeat_length=m)
    x = _normal(6, (m, k), 1.0)
    w = _normal(7, (2, k, n), k ** -0.5)
    ct = _normal(8, (m, n), 1.0)
    held = jax.nn.one_hot(gid - 1, 2)     # zero rows for experts 0, 3

    def einsum(x, w):
        return jnp.einsum("mk,gkn,mg->mn", x, w, held,
                          precision=jax.lax.Precision.HIGHEST)

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w) * ct)

    def gmm(x, w):
        return grouped_matmul(x, w, sizes, 1, fused=fused)
    if fused:
        with pltpu.force_tpu_interpret_mode():
            got = jax.value_and_grad(loss(gmm), argnums=(0, 1))(x, w)
    else:
        got = jax.value_and_grad(loss(gmm), argnums=(0, 1))(x, w)
    want = jax.value_and_grad(loss(einsum), argnums=(0, 1))(x, w)
    for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, e, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fused", [False, True])
def test_grouped_matmul_vjp_with_pad_rows(fused):
    # the held layer's buffer: the rows of 2 held experts, then 64 rows
    # past the count that no held expert owns, whose outputs are zero
    sizes = jnp.array([24, 40, 64], jnp.int32)
    m, k, n = 128, 128, 256
    gid = jnp.repeat(jnp.arange(3), sizes, total_repeat_length=m)
    x = _normal(15, (m, k), 1.0)
    w = _normal(16, (2, k, n), k ** -0.5)
    ct = _normal(17, (m, n), 1.0)
    held = jax.nn.one_hot(gid, 2)         # zero rows for the pad group

    def einsum(x, w):
        return jnp.einsum("mk,gkn,mg->mn", x, w, held,
                          precision=jax.lax.Precision.HIGHEST)

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w) * ct)

    def gmm(x, w):
        return grouped_matmul(x, w, sizes, 0, fused=fused)
    if fused:
        with pltpu.force_tpu_interpret_mode():
            out = gmm(x, w)
            got = jax.value_and_grad(loss(gmm), argnums=(0, 1))(x, w)
    else:
        out = gmm(x, w)
        got = jax.value_and_grad(loss(gmm), argnums=(0, 1))(x, w)
    assert not np.asarray(out[64:]).any()
    want = jax.value_and_grad(loss(einsum), argnums=(0, 1))(x, w)
    for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, e, rtol=1e-4, atol=1e-4)


def test_training_step_matches_reference():
    # three steps of the program's own training step (build_step) and of
    # the reference from the same seeded weights and tokens: each loss,
    # the first gradient's leaf norms and the parameters' change
    import gaps
    import seedweights
    from lanes import train_pjit
    from repro.models import init
    from repro.optim import adamw_init
    c = small_config()
    cfg = train_pjit.program_config(c)
    avals = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    assert (seedweights.names_and_shapes(avals)
            == seedweights.names_and_shapes(mla_moe_lm.param_avals(c)))
    seed, batch, seq = 2**33 + 5, 2, 64
    step = train_pjit.program_step(cfg, c)
    params = seedweights.maker(avals)(seed)
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    losses, grads = [], None
    for i in range(3):
        b = train_pjit.ref_tokens.batch_at(128, seed, i, batch, seq)
        state, metrics = step(state, b)
        losses.append(float(metrics["loss"]))
        assert metrics["moe_rows_held"] > 0
        if i == 0:
            grads = {n: v / 0.1 for n, v in gaps.to_host(
                gaps.leaf_norms(state["opt"]["m"])).items()}
    change = gaps.to_host(gaps.change_norms(state["params"],
                                            seedweights.seed_key(seed)))
    ref = train_pjit.reference_readings(c, seed, batch, seq)
    assert gaps.loss_gap(losses, ref["losses"]) < 1e-5
    assert gaps.worst_leaf_gap(grads, ref["grad_norms"]) < 1e-4
    # Adam divides by the gradient's own size, so the change of a leaf
    # whose gradient is near nought carries its rounding whole
    keep = gaps.moved_by_gradient(ref["grad_norms"])
    assert gaps.worst_leaf_gap(change, ref["change_norms"], keep) < 1e-3
