"""Share of its roofline that the training step's latent attention
reaches: the splash forward, dK/dV and dQ Pallas kernels' work
(``rooflines/mla_attention.py``) at the chip's peak, over the busy
union of their device ops, averaged over the cell's chips."""
from __future__ import annotations

from scopes import roofline_share


def read(r: dict):
    return roofline_share(r, "mla_attention")
