"""Share of the device's busy time that no op under one of the program's
scopes (``attn``, ``mlp``, ``lm_head_ce``, ``adamw``) covers: the layer
scan's own stacking copies, the embedding, the residual adds, the
schedule; averaged over the cell's chips."""
from __future__ import annotations

from scopes import scoped_reading


def read(r: dict):
    sr = scoped_reading(r)
    if sr is None or sr.busy_ns <= 0:
        return None
    return 100.0 * sr.unscoped_ns / sr.busy_ns
