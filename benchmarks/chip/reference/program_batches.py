"""The IR lane's batch stream, written out again for the reference: the
same algorithm as the program's ``launch.train._ProgramLoader`` (per
step a Philox stream keyed by the seed, counter ``[0, 0, 2, step]``;
inputs in name order, standard normal in the input's dtype)."""
from __future__ import annotations

import numpy as np


def batch_at(shapes: dict, seed: int, step: int) -> dict:
    rng = np.random.Generator(np.random.Philox(key=seed,
                                               counter=[0, 0, 2, step]))
    out = {}
    for name, (shape, dtype) in sorted(shapes.items()):
        dt = np.dtype(dtype)
        if np.issubdtype(dt, np.integer):
            raise ValueError(f"input {name} is not real-valued")
        out[name] = rng.standard_normal(shape).astype(dt)
    return out
