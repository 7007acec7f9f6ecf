"""Cost model for the timeline simulator (TPU v5e target constants).

Chunk compute cost comes from XLA itself: each chunk's exec function is
lowered once on CPU and ``cost_analysis()`` supplies FLOPs and bytes
accessed — the same source the dry-run roofline uses.  Comm cost uses
standard ring/all-to-all models over ICI.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax

# TPU v5e (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link (task spec: ~50 GB/s/link)
ICI_LAT = 1e-6                  # s per hop
DCN_BW = 25e9                   # B/s per host, cross-pod
DMA_BW = 25e9                   # B/s host<->device (offload round-trips)


@dataclass
class CostModel:
    peak_flops: float = PEAK_FLOPS_BF16
    hbm_bw: float = HBM_BW
    ici_bw: float = ICI_BW
    dcn_bw: float = DCN_BW
    dma_bw: float = DMA_BW       # host DMA for d2h/h2d offload nodes
    mfu: float = 0.55            # achievable fraction of peak on chunks
    comm_latency: float = ICI_LAT

    # ---------------- chunk costs -----------------------------------------
    def chunk_seconds(self, node, params, sample_inputs) -> float:
        """Roofline max(compute, memory) time for a chunk exec function."""
        flops, bytes_ = analyze_fn(node.fn, params.get(node.bucket)
                                   if node.bucket else None, sample_inputs)
        t_c = flops / (self.peak_flops * self.mfu)
        t_m = bytes_ / self.hbm_bw
        return max(t_c, t_m, 1e-7)

    # ---------------- comm costs (size only; contention in simulator) -----
    def comm_bytes_on_wire(self, op: str, nbytes: int, group: int) -> int:
        """Bytes each participant moves over its link.  d2h/h2d offload
        round-trips move each device's shard over the host DMA link —
        expressed in ICI-equivalent bytes so the simulator's fluid-flow
        rate (``ici_bw`` fair-share) yields ``shard_bytes / dma_bw``."""
        if op in ("d2h", "h2d"):
            shard = nbytes / max(group, 1)
            return int(shard * (self.ici_bw / self.dma_bw))
        if group <= 1:
            return 0
        n = group
        if op == "all_reduce":
            return int(2 * nbytes * (n - 1) / n)
        if op in ("all_gather", "reduce_scatter"):
            return int(nbytes * (n - 1) / n)
        if op == "all_to_all":
            return int(nbytes * (n - 1) / n)
        if op == "p2p":
            return int(nbytes)
        return int(nbytes)

    def link_bw(self, cross_pod: bool = False) -> float:
        return self.dcn_bw if cross_pod else self.ici_bw


_ANALYSIS_CACHE: dict[Any, tuple[float, float]] = {}


def analyze_fn(fn, bucket_params, sample_inputs) -> tuple[float, float]:
    """(flops, bytes_accessed) of a chunk exec function via the default
    backend's XLA cost analysis.  Cached on (fn identity, input avals)."""
    avals = tuple(
        (tuple(x.shape), str(x.dtype)) for x in sample_inputs
        if x is not None)
    key = (id(fn), avals)
    if key in _ANALYSIS_CACHE:
        return _ANALYSIS_CACHE[key]
    specs = [jax.ShapeDtypeStruct(x.shape, x.dtype)
             if x is not None else None for x in sample_inputs]
    pspec = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), bucket_params)

    def wrapped(p, *ins):
        return fn(p, *ins)

    try:
        lowered = jax.jit(wrapped).lower(pspec, *specs)
    except (ValueError, TypeError):
        # the samples come from logical edge specs, which for some
        # backward chunks disagree with the per-device shapes the chunk
        # traces at (a cotangent of a DP-split output): the chunk cannot
        # be traced at them, so estimate from the sizes instead
        nbytes = sum(x.size * x.dtype.itemsize for x in sample_inputs
                     if x is not None)
        if bucket_params is not None:
            nbytes += sum(l.size * l.dtype.itemsize for l in
                          jax.tree_util.tree_leaves(bucket_params))
        flops = 2.0 * nbytes
    else:
        # a chunk that traces but does not compile is an error to
        # report, not a cost to guess
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        if not ca:
            raise RuntimeError(
                f"XLA gave no cost analysis for chunk function {fn!r} on "
                f"platform {jax.default_backend()}")
        flops = float(ca.get("flops", 0.0))
        nbytes = float(ca.get("bytes accessed", 0.0))
    _ANALYSIS_CACHE[key] = (flops, nbytes)
    return flops, nbytes
