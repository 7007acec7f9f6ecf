"""shard_map all-to-all MoE (moe_block_ep) numerics vs the dense-dispatch
oracle on 8 simulated devices (subprocess, mesh (2,4))."""
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # 8-device subprocess; scripts/tier1.sh skips

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import _mk
    from repro.models import layers as L

    mesh = _mk((2, 4), ("data", "model"))
    E, K, D, DEX = 8, 2, 16, 32
    B, S = 4, 16
    p = L.init_moe(jax.random.PRNGKey(0), D, DEX, E, 0, "swiglu",
                   jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D)) * 0.5

    kw = dict(n_experts=E, top_k=K, act="swiglu", capacity_factor=8.0)
    with jax.set_mesh(mesh):
        def f_ep(p, x):
            y, aux = L.moe_block_ep(p, x, mesh=mesh, dp_axes=("data",),
                                    tp_axis="model", **kw)
            return jnp.sum(y ** 2), (y, aux)
        (loss_ep, (y_ep, aux_ep)), g_ep = jax.value_and_grad(
            f_ep, has_aux=True)(p, x)

    def f_dense(p, x):
        y, aux = L.moe_block_dense(p, x, **kw)
        return jnp.sum(y ** 2), (y, aux)
    (loss_d, (y_d, aux_d)), g_d = jax.value_and_grad(
        f_dense, has_aux=True)(p, x)

    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_d),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(float(aux_ep), float(aux_d), atol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3),
        g_ep, g_d)
    print("MOE_EP_OK", float(loss_ep), float(loss_d))
""")


def test_moe_ep_matches_dense():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu", "HOME": "/root"})
    assert "MOE_EP_OK" in r.stdout, (r.stdout[-1500:], r.stderr[-3000:])
