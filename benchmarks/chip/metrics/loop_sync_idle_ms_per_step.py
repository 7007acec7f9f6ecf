"""Device idle time per measured step while the program's ``ft.sync``
span (``Supervisor.run``'s ``block_until_ready``) is open: the holes
between the step's own ops while the host waits for it, and any wait for
the step to start or to hand back its outputs; averaged over the cell's
chips."""
from __future__ import annotations

from scopes import idle_ms_per_step


def read(r: dict):
    return idle_ms_per_step(r, "ft.sync")
