"""The whole step's share of the chip's peak while the device is busy:
model FLOPs of the window's tokens (the configuration's ``flops_rule``,
as ``mfu`` counts them, recomputation left out) over the device's busy
time in the traced window times the peak, for all of the cell's chips.
It bounds what a kernel's roofline share can give the step."""
from __future__ import annotations

from cellspec import flops_per_token


def read(r: dict):
    red = r["reduced"]
    if red is None or red.busy_ns <= 0:
        return None
    out, cell = r["out"], r["cell"]
    flops = flops_per_token(cell, out["lane_info"]) * out["tokens"]
    busy_s = red.busy_ns * 1e-9 * red.n_devices
    return 100.0 * flops / (busy_s * r["peaks"]["bf16_flops_per_s"])
