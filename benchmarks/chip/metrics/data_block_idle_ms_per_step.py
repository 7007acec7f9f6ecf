"""Device idle time per measured step while the program's ``data.block``
span is open: the part of the token source's host work that the chip
waits for, averaged over the cell's chips."""
from __future__ import annotations

from scopes import idle_ms_per_step


def read(r: dict):
    return idle_ms_per_step(r, "data.block")
