"""Share of the device's busy time that no op under one of the program's
scopes covers: the layer scan's own stacking copies, the embedding, the
residual adds, the schedule; averaged over the cell's chips.

The program's scopes are the four that its training step names
(``models/model.py``, ``optim/adamw.py``), listed here in ``SCOPES`` and
nowhere else: a reader of a new scope leaves this share as it is, and
the share counts the new scope's time as its own until ``SCOPES`` names
it, a change to this file."""
from __future__ import annotations

from scopes import scoped_reading

SCOPES = ("attn", "mlp", "lm_head_ce", "adamw")


def read(r: dict):
    sr = scoped_reading(r, SCOPES)
    if sr is None or sr.busy_ns <= 0:
        return None
    return 100.0 * sr.unscoped_ns(SCOPES) / sr.busy_ns
