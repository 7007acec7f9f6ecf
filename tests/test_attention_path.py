"""Which path the model's attention takes, and the fused path's numbers.

``layers.attention_path`` sends an attention call of
``layers.attention_block`` to JAX's fused Pallas flash kernels
(``kernels.train_attention``) only on a TPU, without a KV cache, a
registered impl or a window, at lengths the kernels tile, on one
device; every call is tallied under its path.  Off a TPU every call
falls back by ``platform``.  The tests steer ``jax.default_backend`` to
"tpu" and ``jax.device_count`` to the host's devices, and run the
kernels interpreted (``pltpu.force_tpu_interpret_mode``), against
``naive_attention`` in float32.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AbstractMesh, AxisType

from repro.configs import get_config
from repro.kernels.train_attention import block_sizes, train_attention
from repro.models import layers as L
from repro.models.attention import flash_attention_ref, naive_attention

F32 = jnp.float32


@pytest.fixture
def on_tpu(monkeypatch):
    """A host of one TPU; ``on_tpu(n)`` makes it ``n``."""
    def host(devices):
        monkeypatch.setattr(jax, "device_count", lambda: devices)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    host(1)
    return host


def _cfg(n_heads=2, n_kv=None, head_dim=64):
    return get_config("qwen1.5-0.5b").reduced(
        n_layers=1, d_model=n_heads * head_dim, d_ff=256, vocab=256,
        n_heads=n_heads, n_kv_heads=n_kv or n_heads)


def _params(cfg):
    return jax.eval_shape(lambda: L.init_attn(
        jax.random.PRNGKey(0), cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
        cfg.head_dim, cfg.qkv_bias, F32))


def _paths(fn, *args) -> dict:
    """The paths the attention calls traced by ``fn(*args)`` took."""
    before = collections.Counter(L.attention_paths())
    jax.eval_shape(fn, *args)
    return dict(collections.Counter(L.attention_paths()) - before)


def _block_paths(cfg, seq=256, batch=2, **kw) -> dict:
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), F32)
    return _paths(lambda p, x: L.attention_block(p, x, cfg, **kw)[0],
                  _params(cfg), x)


def test_cpu_falls_back_by_platform():
    assert _block_paths(_cfg()) == {"platform": 1}


@pytest.mark.parametrize("n_heads,n_kv,head_dim", [
    (2, 2, 64), (4, 2, 64), (4, 1, 128), (2, 2, 80)])
def test_eligible_call_is_fused(on_tpu, n_heads, n_kv, head_dim):
    assert _block_paths(_cfg(n_heads, n_kv, head_dim)) == {"fused": 1}


def test_kv_cache_falls_back(on_tpu):
    cfg = _cfg()
    cache = jax.ShapeDtypeStruct((2, cfg.n_kv_heads, 512, cfg.head_dim), F32)
    x = jax.ShapeDtypeStruct((2, 1, cfg.d_model), F32)
    got = _paths(lambda p, x, ck, cv: L.attention_block(
        p, x, cfg, kv_cache=(ck, cv), cache_len=7)[0],
        _params(cfg), x, cache, cache)
    assert got == {"kv_cache": 1}


def test_registered_impl_wins(on_tpu):
    L.register_impl("attention", flash_attention_ref)
    try:
        assert _block_paths(_cfg()) == {"registered": 1}
    finally:
        L._IMPLS.pop("attention")


def test_window_falls_back(on_tpu):
    assert _block_paths(_cfg(), window=64) == {"window": 1}


@pytest.mark.parametrize("seq,head_dim", [(200, 64), (320, 128)])
def test_untiled_shape_falls_back(on_tpu, seq, head_dim):
    assert _block_paths(_cfg(head_dim=head_dim), seq=seq) == {"shape": 1}


@pytest.mark.parametrize("seq", [1000, 1500])
def test_shape_rule(on_tpu, seq):
    # whisper-large-v3's encoder runs 1500 frames
    assert L.attention_path(seq, kv_cache=False, window=None) == "shape"


@pytest.mark.parametrize("attn_tp", [False, True])
def test_sharded_falls_back(on_tpu, attn_tp):
    mesh = AbstractMesh((2, 2), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    L.set_axis_map({"dp": "data", "tp": "model", "attn_tp": attn_tp,
                    "mesh": mesh})
    try:
        with jax.sharding.use_abstract_mesh(mesh):
            got = _block_paths(_cfg())
    finally:
        L.set_axis_map(None)
    assert got == {"sharded": 1}


def test_axis_map_that_splits_nothing_is_sharded(on_tpu):
    # batch 3 is not divided by the 2-way data axis, so q, k and v stay
    # whole; the step still runs over two devices, and the compiler
    # lowers no Pallas kernel in a step over more than one
    mesh = AbstractMesh((2,), ("data",), axis_types=(AxisType.Auto,))
    L.set_axis_map({"dp": "data", "mesh": mesh})
    try:
        with jax.sharding.use_abstract_mesh(mesh):
            got = _block_paths(_cfg(), batch=3)
    finally:
        L.set_axis_map(None)
    assert got == {"sharded": 1}


@pytest.mark.parametrize("devices,path", [(1, "fused"), (4, "sharded")])
def test_context_mesh_decides(on_tpu, devices, path):
    # a mesh in the context, and none in the axis map: its size decides,
    # not the host's eight devices
    on_tpu(8)
    mesh = AbstractMesh((devices,), ("data",), axis_types=(AxisType.Auto,))
    with jax.sharding.use_abstract_mesh(mesh):
        assert _block_paths(_cfg()) == {path: 1}


@pytest.mark.parametrize("devices,path", [(1, "fused"), (4, "sharded")])
def test_without_a_mesh_the_host_decides(on_tpu, devices, path):
    # no mesh anywhere: a jit may still be given inputs sharded over
    # every device of the host
    on_tpu(devices)
    assert _block_paths(_cfg()) == {path: 1}


@pytest.mark.parametrize("seq,block", [
    (2048, 512), (4096, 512), (768, 256), (384, 128), (200, None)])
def test_block_sizes(seq, block):
    bs = block_sizes(seq)
    if block is None:
        assert bs is None
        return
    assert {bs.block_q, bs.block_k_major, bs.block_k, bs.block_q_major_dkv,
            bs.block_k_major_dkv, bs.block_k_dkv, bs.block_q_dkv,
            bs.block_k_major_dq, bs.block_k_dq, bs.block_q_dq} == {block}


def test_build_step_prints_paths(capsys):
    from repro.launch.train import build_step
    from repro.models import init
    from repro.optim import adamw_init
    cfg = _cfg()
    params = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    state = {"params": params, "opt": jax.eval_shape(adamw_init, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    batch = {k: jax.ShapeDtypeStruct((2, 128), jnp.int32)
             for k in ("tokens", "labels")}
    jax.eval_shape(build_step(cfg, lambda s: 1e-3), state, batch)
    assert 'attention paths: {"platform": 1}' in capsys.readouterr().err


def _rand(i, shape):
    return jax.random.normal(jax.random.PRNGKey(i), shape, F32)


@pytest.mark.parametrize("seq,head_dim,hq,hkv,causal", [
    (256, 64, 2, 2, True),
    (256, 128, 2, 2, False),
    (512, 64, 4, 2, True),       # GQA
    (512, 128, 2, 1, False),     # MQA
    (1024, 64, 1, 1, True),      # two blocks a side: one skipped
])
def test_fused_matches_naive(seq, head_dim, hq, hkv, causal):
    q = _rand(0, (1, hq, seq, head_dim))
    k = _rand(1, (1, hkv, seq, head_dim))
    v = _rand(2, (1, hkv, seq, head_dim))
    do = _rand(3, (1, hq, seq, head_dim))

    def fused(q, k, v):
        return train_attention(q, k, v, causal=causal)

    def naive(q, k, v):
        return naive_attention(q, k, v, causal=causal)

    with pltpu.force_tpu_interpret_mode():
        got, vjp = jax.vjp(fused, q, k, v)
        got_grads = vjp(do)
    want, vjp = jax.vjp(naive, q, k, v)
    want_grads = vjp(do)
    for a, b in zip((got, *got_grads), (want, *want_grads)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


def test_fused_refuses_a_window():
    q = jax.ShapeDtypeStruct((1, 2, 256, 64), F32)
    with pytest.raises(ValueError, match="window"):
        jax.eval_shape(lambda q: train_attention(q, q, q, window=64), q)


def _mla_cfg():
    return get_config("moonlight-16b-a3b").reduced(
        n_layers=2, d_model=128, d_ff=256, vocab=256, n_heads=2)


def _mla_block_paths(**kw) -> dict:
    cfg = _mla_cfg()
    p = jax.eval_shape(lambda: L.init_mla(
        jax.random.PRNGKey(0), cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, F32))
    x = jax.ShapeDtypeStruct((2, 256, cfg.d_model), F32)
    return _paths(lambda p, x: L.attention_block(p, x, cfg, **kw)[0], p, x)


@pytest.mark.parametrize("tpu,path", [(True, "mla_fused"),
                                      (False, "mla_platform")])
def test_latent_attention_is_tallied_apart(monkeypatch, tpu, path):
    if tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert _mla_block_paths() == {path: 1}


def test_latent_attention_matches_naive(monkeypatch):
    # the splash kernels, interpreted, at d_qk 48 and d_v 32 against
    # naive attention scaled by d_qk^-1/2; q pre-scaled, no padding
    from jax.experimental.pallas.ops.tpu.splash_attention import \
        splash_attention_kernel as splash
    from jax.experimental.pallas.ops.tpu.splash_attention import \
        splash_attention_mask as splash_mask

    from repro.kernels import train_attention as ta

    def interpreted(heads, seq, block):
        mask = splash_mask.MultiHeadMask(
            [splash_mask.CausalMask((seq, seq)) for _ in range(heads)])
        sizes = splash.BlockSizes(
            block_q=block, block_kv=block, block_kv_compute=block,
            block_q_dkv=block, block_kv_dkv=block,
            block_kv_dkv_compute=block, block_q_dq=block, block_kv_dq=block)
        return splash.make_splash_mha_single_device(
            mask, block_sizes=sizes, interpret=True)
    monkeypatch.setattr(ta, "_splash_kernel", interpreted)
    q, k = _rand(0, (1, 2, 256, 48)), _rand(1, (1, 2, 256, 48))
    v, do = _rand(2, (1, 2, 256, 32)), _rand(3, (1, 2, 256, 32))
    got, vjp = jax.vjp(ta.latent_attention, q, k, v)
    got_grads = vjp(do)
    want, vjp = jax.vjp(lambda q, k, v: naive_attention(q, k, v), q, k, v)
    want_grads = vjp(do)
    for a, b in zip((got, *got_grads), (want, *want_grads)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
