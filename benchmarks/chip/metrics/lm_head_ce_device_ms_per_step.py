"""Device time per measured step of the ops under the program's
``lm_head_ce`` scope (the final norm, the LM head's logits and the
cross-entropy, forward and backward): their busy union over the traced
window, averaged over the cell's chips.

It reads low where the compiler adds ops of its own: where the vocabulary
is not a multiple of 128 (minicpm's 122753), the backward of the gold
logit's gather relayouts the f32 logits' gradient to a flat buffer and
back in two loops of row copies (``dynamic-update-slice``), which carry
no op name and count as unscoped."""
from __future__ import annotations

from scopes import scope_ms_per_step


SCOPE = "lm_head_ce"


def read(r: dict):
    return scope_ms_per_step(r, SCOPE)
