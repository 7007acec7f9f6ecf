"""Device time per measured step: the union of the intervals in which an
XLA operation ran on the chip, over the traced window, per step, averaged
over the cell's chips."""
from __future__ import annotations


def read(r: dict):
    red = r["reduced"]
    if red is None or red.busy_ns <= 0:
        return None
    return red.busy_ns * 1e-6 / r["out"]["steps"]
