"""Device time per measured step of the ops under the program's ``attn``
scope (the pre-attention norm and the attention block, in the forward
pass, its remat recompute and the backward pass): their busy union over
the traced window, averaged over the cell's chips."""
from __future__ import annotations

from scopes import scope_ms_per_step


SCOPE = "attn"


def read(r: dict):
    return scope_ms_per_step(r, SCOPE)
