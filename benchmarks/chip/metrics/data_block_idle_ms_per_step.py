"""Device idle time per measured step while the program's ``data.block``
span is open: the part of the token source's host work that the chip
waits for, averaged over the cell's chips."""
from __future__ import annotations

from scopes import span_reading


def read(r: dict):
    sr = span_reading(r, "data.block")
    if sr is None:
        return None
    return sr.idle_under_ns["data.block"] * 1e-6 / r["out"]["steps"]
