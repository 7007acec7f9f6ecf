"""Model FLOPs per token of the IR lane's two-matmul proxy: 6 x the
matmul parameters (``w1``, ``w2`` of every stage) multiplied per token,
forward and backward.  The inert ``bank`` leaves hold resident bytes
only and are not counted.  The lane reports the matmul parameters from
the compiled program's own shapes."""
from __future__ import annotations


def per_token(config: dict, traffic: dict, lane_info: dict) -> float:
    return 6.0 * lane_info["matmul_params"]
