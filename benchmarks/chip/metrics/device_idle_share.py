"""Share of the traced window in which no XLA operation ran on the chip,
averaged over the cell's chips."""
from __future__ import annotations


def read(r: dict):
    red = r["reduced"]
    if red is None or red.window_ns <= 0 or red.busy_ns <= 0:
        return None
    return 100.0 * (1.0 - red.busy_ns / red.window_ns)
