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
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_fwd_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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
