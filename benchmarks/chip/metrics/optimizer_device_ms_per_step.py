"""Device time per measured step of the ops under the program's
``adamw`` scope (``adamw_update``: the global norm, the clip and the
update of every parameter and both moments): their busy union over the
traced window, averaged over the cell's chips."""
from __future__ import annotations

from scopes import scope_ms_per_step


SCOPE = "adamw"


def read(r: dict):
    return scope_ms_per_step(r, SCOPE)
