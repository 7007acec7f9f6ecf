"""The Pallas kernels compile for a TPU v5e at the widths of the shipped
configs.

Nothing runs: each kernel is lowered for one chip of a described (not
attached) ``v5e:2x2`` topology and compiled by the TPU compiler, which
refuses what interpret mode accepts — block shapes off the (8, 128)
tiling, lane indices it cannot prove aligned, scratch beyond VMEM.  The
topology is described inside a fixture, never on import, so every test
worker collects the same tests and only the one given this file loads
the TPU library.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_fwd_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.train_attention import train_attention
from repro.models import init, train_loss
from repro.models import layers as L


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (4, 16, 16, 1024, 64),      # qwen1.5-0.5b
    (1, 16, 8, 4096, 128),      # qwen3-1b, GQA
])
def test_flash_attention_fwd(one_chip, b, hq, hkv, s, d):
    _compile(lambda q, k, v: flash_attention_fwd_pallas(
        q, k, v, causal=True, interpret=False), one_chip,
        ((b, hq, s, d), BF16), ((b, hkv, s, d), BF16),
        ((b, hkv, s, d), BF16))


@pytest.mark.parametrize("b,h,s,d", [
    (4, 16, 2048, 64),          # qwen1.5-0.5b, seq 2048 x batch 4
    (1, 36, 4096, 64),          # minicpm-2b, seq 4096 x batch 1
])
def test_train_attention_grad(one_chip, b, h, s, d):
    # the training step's fused attention, forward and both backward
    # kernels, at the blocks ``train_attention.block_sizes`` chooses
    def loss(q, k, v):
        out = train_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(F32))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((b, h, s, d), BF16), ((b, h, s, d), BF16),
                    ((b, h, s, d), BF16))
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("mesh_seen_by", ["axis_map", "context", "none"])
def test_train_step_over_four_chips(v5e_2x2, monkeypatch, mesh_seen_by):
    # a training step whose batch is split over the four chips: the TPU
    # compiler does not partition a Pallas kernel, so the step lowers
    # only if its attention leaves the fused path (by "sharded"),
    # whether the mesh is in the axis map, in the context, or only in
    # the jit's shardings on a host of four chips
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: len(v5e_2x2.devices))
    mesh = Mesh(np.array(v5e_2x2.devices).reshape(2, 2), ("data", "model"))
    cfg = get_config("qwen1.5-0.5b").reduced(
        n_layers=2, d_model=128, d_ff=256, vocab=256, n_heads=2,
        n_kv_heads=2)
    params = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, P())), params)
    batch = {k: jax.ShapeDtypeStruct((4, 256), jnp.int32,
                                     sharding=NamedSharding(mesh, P("data")))
             for k in ("tokens", "labels")}
    step = jax.jit(jax.grad(lambda p, b: train_loss(cfg, p, b)))
    before = collections.Counter(L.attention_paths())
    if mesh_seen_by == "axis_map":
        L.set_axis_map({"dp": "data", "tp": "model", "mesh": mesh})
    try:
        if mesh_seen_by == "none":
            lowered = step.lower(params, batch)
        else:
            with jax.set_mesh(mesh):
                lowered = step.lower(params, batch)
    finally:
        L.set_axis_map(None)
    assert set(collections.Counter(L.attention_paths()) - before) == {
        "sharded"}
    assert "tpu_custom_call" not in lowered.compile().as_text()


def test_moonlight_cell_step_fits_one_chip(one_chip, monkeypatch):
    # the benchmark cell's training step at its cut widths (Moonlight-
    # 16B-A3B, 6 of 27 layers, 8 of 64 experts held, 20480 vocabulary
    # rows, 2 x 8192 tokens): latent attention on the splash kernels,
    # the experts on the megablox kernels, within the chip's 16 GB
    import dataclasses
    from repro.launch.train import build_step
    from repro.optim import adamw_init, cosine_schedule
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    base = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(base, n_layers=6, vocab=20480,
                              moe=dataclasses.replace(base.moe, n_held=8))

    def place(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    state = jax.tree.map(place, {
        "params": params, "opt": jax.eval_shape(adamw_init, params),
        "step": jax.ShapeDtypeStruct((), jnp.int32)})
    batch = {k: place(jax.ShapeDtypeStruct((2, 8192), jnp.int32))
             for k in ("tokens", "labels")}
    before = collections.Counter(L.attention_paths())
    compiled = build_step(cfg, cosine_schedule(3e-4, 100)).lower(
        state, batch).compile()
    assert set(collections.Counter(L.attention_paths()) - before) == {
        "mla_fused"}
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < 14.9 * 2**30, used / 2**30
    text = compiled.as_text().splitlines()
    kernels = collections.Counter(
        line.split("=")[0].strip().lstrip("%").rsplit(".", 1)[0]
        for line in text
        if 'custom_call_target="tpu_custom_call"' in line)
    assert {"splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals",
            "splash_mha_dq_no_residuals", "gmm", "tgmm"} <= set(kernels)
    # the expert layer chooses its row buffer's size on the device
    assert any(" conditional(" in line and "/moe/" in line for line in text)


def test_rmsnorm_rows_not_a_multiple_of_8(one_chip):
    _compile(lambda x, w: rmsnorm_pallas(x, w, interpret=False),
             one_chip, ((4, 1001, 1024), BF16), ((1024,), BF16))


def test_moe_gmm_deepseek_moe_16b(one_chip):
    # 64 routed experts, d_model 2048, d_expert 1408
    _compile(lambda x, w: moe_gmm_pallas(x, w, interpret=False),
             one_chip, ((64, 256, 2048), BF16), ((64, 2048, 1408), BF16))


def test_mamba_scan_falcon_mamba_7b(one_chip):
    # d_inner = 2 x 4096 channels, state 16, 2048 steps
    b, s, c, n = 1, 2048, 8192, 16
    _compile(lambda xz, dt, A, B, C, D: mamba_scan_pallas(
        xz, dt, A, B, C, D, interpret=False), one_chip,
        ((b, s, c), F32), ((b, s, c), F32), ((c, n), F32),
        ((b, s, n), F32), ((b, s, n), F32), ((c,), F32))
