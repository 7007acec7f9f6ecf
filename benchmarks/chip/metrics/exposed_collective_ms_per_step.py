"""Collective time per measured step that no compute hides: the time of
all-gather, all-reduce, reduce-scatter, collective-permute and
all-to-all operations (synchronous, or asynchronous between their start
and done) in which no other operation ran on that chip, averaged over
the cell's chips."""
from __future__ import annotations


def read(r: dict):
    red = r["reduced"]
    if red is None or red.busy_ns <= 0:
        return None
    return red.exposed_collective_ns * 1e-6 / r["out"]["steps"]
