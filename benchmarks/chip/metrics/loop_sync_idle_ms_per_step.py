"""Device idle time per measured step while the program's ``ft.sync``
span (``Supervisor.run``'s ``block_until_ready``) is open: the holes
between the step's own ops while the host waits for it, and any wait for
the step to start or to hand back its outputs; averaged over the cell's
chips."""
from __future__ import annotations

from scopes import span_reading


def read(r: dict):
    sr = span_reading(r, "ft.sync")
    if sr is None:
        return None
    return sr.idle_under_ns["ft.sync"] * 1e-6 / r["out"]["steps"]
