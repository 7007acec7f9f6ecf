"""Host time of the program's loader (``data/pipeline``) per measured
step: the benchmark's ``loader`` span around ``next_batch``, which the
training loop waits for before each dispatch."""
from __future__ import annotations


def read(r: dict):
    lo, hi = r["out"]["window_host"]
    d = r["spans"].durations("loader", lo, hi)
    return 1e3 * sum(d) / r["out"]["steps"] if d else None
