"""Host time of the Piper compiler in set-up: the benchmark's
``ir.compile`` span around ``tune.build_strategy_program``
(``compile_training``, its passes and its certifier)."""
from __future__ import annotations


def read(r: dict):
    d = r["spans"].durations("ir.compile")
    return sum(d) if d else None
