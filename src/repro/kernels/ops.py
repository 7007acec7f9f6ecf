"""Jitted wrappers for the Pallas kernels + impl-registry hookup.

On TPU the kernels compile natively; on the CPU they run with
``interpret=True`` (the kernel body executes step-by-step), which is how
their correctness is tested.  Any other platform is refused rather than
silently interpreted.  ``register_kernels`` swaps them into the model
layers' impl registry.
"""
from __future__ import annotations

import functools

import jax

from ..models import layers as L
from ..models.attention import _flash_bwd
from .flash_attention import flash_attention_fwd_pallas
from .mamba_scan import mamba_scan_pallas
from .moe_gmm import moe_gmm_pallas
from .rmsnorm import rmsnorm_pallas


def _interpret() -> bool:
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels target TPU; platform {platform} has no "
            "compiled path (only the CPU runs them interpreted)")
    return platform == "cpu"


# ---- flash attention: Pallas forward + jnp flash backward (custom VJP)

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, q_offset, block_kv):
    return flash_attention_fwd_pallas(q, k, v, causal=causal,
                                      q_offset=q_offset,
                                      block_kv=block_kv,
                                      interpret=_interpret())


def _flash_fwd_rule(q, k, v, causal, q_offset, block_kv):
    out = flash_attention_fwd_pallas(q, k, v, causal=causal,
                                     q_offset=q_offset,
                                     block_kv=block_kv,
                                     interpret=_interpret())
    # recompute lse in the backward (flash bwd needs it); cheap relative
    # to storing per-block probabilities
    from ..models.attention import _flash_fwd_impl
    _, lse = _flash_fwd_impl(q, k, v, causal, q_offset, None, block_kv)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, q_offset, block_kv, res, dout):
    return _flash_bwd(causal, q_offset, None, block_kv, False, res, dout)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal=True, q_offset=0, sm_scale=None,
                    window=None, block_kv=128):
    if window is not None:
        # windowed variant not kernelized yet -> jnp flash path
        from ..models.attention import flash_attention_ref
        return flash_attention_ref(q, k, v, causal=causal,
                                   q_offset=q_offset, window=window,
                                   block_kv=block_kv)
    return _flash(q, k, v, causal, q_offset, block_kv)


def rmsnorm(x, w, eps=1e-6):
    # eps must stay a python float (the kernel closes over it)
    return rmsnorm_pallas(x, w, float(eps), interpret=_interpret())


def moe_gmm(x, w):
    return moe_gmm_pallas(x, w, interpret=_interpret())


def mamba_scan(xz, dt, A, B, C, D, h0=None, chunk=None):
    return mamba_scan_pallas(xz, dt, A, B, C, D, h0=h0,
                             interpret=_interpret())


def register_kernels(attention=True, norm=True, gmm=True,
                     scan=True) -> None:
    """Install the Pallas kernels as the model-layer implementations."""
    if attention:
        L.register_impl("attention", flash_attention)
    if norm:
        L.register_impl("rmsnorm", lambda x, w, eps=1e-6:
                        rmsnorm(x, w, eps))
    if gmm:
        L.register_impl("moe_gmm", moe_gmm)
    if scan:
        L.register_impl("mamba_scan", mamba_scan)


def unregister_kernels() -> None:
    for k in ("attention", "rmsnorm", "moe_gmm", "mamba_scan"):
        L._IMPLS.pop(k, None)
