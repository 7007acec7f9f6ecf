"""The benchmark's own host spans.

Each span is a ``jax.profiler.TraceAnnotation`` (so a traced run sees it
on the host plane, on the device's clock) and is also timed on the host
clock here, so an untraced run has the same durations.  Spans are kept
in memory and read when the run ends.
"""
from __future__ import annotations

import time
from contextlib import contextmanager


class Spans:
    def __init__(self) -> None:
        self.records: list[tuple[str, float, float]] = []
        self._open: dict[str, tuple[object, float]] = {}

    @contextmanager
    def span(self, name: str):
        self.open(name)
        try:
            yield
        finally:
            self.close(name)

    def open(self, name: str) -> None:
        """Open a span that ends elsewhere (``close``); at most one span
        of a name is open at a time."""
        from jax.profiler import TraceAnnotation
        ann = TraceAnnotation(name)
        ann.__enter__()
        self._open[name] = (ann, time.perf_counter())

    def close(self, name: str) -> None:
        entry = self._open.pop(name, None)
        if entry is None:
            return
        ann, t0 = entry
        t1 = time.perf_counter()
        ann.__exit__(None, None, None)
        self.records.append((name, t0, t1))

    def durations(self, name: str, lo: float = float("-inf"),
                  hi: float = float("inf")) -> list[float]:
        """Durations in seconds of the ``name`` spans that started in
        ``[lo, hi)`` on the host clock."""
        return [t1 - t0 for n, t0, t1 in self.records
                if n == name and lo <= t0 < hi]
