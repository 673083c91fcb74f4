"""Model FLOPs of the benchmarked transformers, from the configuration's sizes.

The convention of the program's ``core/flops.py`` (copied here so that no
later change to the program moves the yardstick): 2 FLOPs per multiply-add
over the matrix weights (the tied output embedding once, as the unembedding),
plus attention's two products over the attended keys (all keys for the
encoder, on average half for a causal decoder); a training step is three
forward passes.  Recomputation under rematerialisation is not counted.
``d`` is ``reference.dims`` of a configuration.
"""
from __future__ import annotations

from typing import Dict


def matmul_params(d: Dict) -> float:
    E, F, L = d["E"], d["F"], d["L"]
    return float(L * (4 * E * d["H"] * d["D"] + 2 * E * F) + d["Vpad"] * E)


def forward_flops(d: Dict, rows: int, seq: int) -> float:
    tokens = rows * seq
    t_avg = seq / 2 if d["causal"] else seq
    attn = tokens * d["L"] * 2.0 * d["H"] * 2 * d["D"] * t_avg
    return 2.0 * matmul_params(d) * tokens + attn


def train_step_flops(d: Dict, rows: int, seq: int) -> float:
    return 3.0 * forward_flops(d, rows, seq)

