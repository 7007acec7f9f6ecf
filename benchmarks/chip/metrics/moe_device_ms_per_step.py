"""Device time per measured step of the ops under the program's ``moe``
scope (an expert layer's block inside ``mlp``: its routing, the grouped
expert matmuls and the shared experts, in the forward pass, its remat
recompute and the backward pass): their busy union over the traced
window, averaged over the cell's chips."""
from __future__ import annotations

from scopes import scope_ms_per_step


SCOPE = "moe"


def read(r: dict):
    return scope_ms_per_step(r, SCOPE)
