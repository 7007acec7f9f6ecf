"""Device time per measured step of the ops under the program's
``moe_route`` scope (inside ``moe``: the router, its top-k, the sort of
the (token, k) pairs by expert and the gather of their rows, the unsort
and the weighted combine, forward, recompute and backward): their busy
union over the traced window, averaged over the cell's chips."""
from __future__ import annotations

from scopes import scope_ms_per_step


SCOPE = "moe_route"


def read(r: dict):
    return scope_ms_per_step(r, SCOPE)
