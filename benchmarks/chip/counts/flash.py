"""The work of one flash-attention call, as attention defines it and not as
a kernel happens to do it: the FLOPs and HBM bytes that bound
``flash_roofline.l0.train``.

FLOPs: 2 per multiply-add over the useful query-key products only (``k <=
q`` when causal); the forward has two matrix products (scores, and
probabilities times values), the backward five (the scores again, the
probabilities' gradient, and dq, dk, dv).  Bytes: each operand read once and
each result written once, bfloat16 q, k, v, o, do, dq, dk, dv and float32
row statistics (the log-sum-exp, and the backward's ``delta``), one value a
row.  ``bh`` counts the batch rows times the heads; ``s`` queries attend to
``t`` keys of width ``d``.  A kernel that skips masked tiles or narrows its
statistics comes closer to these; none can go below them.
"""
from __future__ import annotations

from typing import Tuple

BF16, F32 = 2, 4


def products(s: int, t: int, causal: bool) -> int:
    """Query-key pairs that count: the lower triangle of a causal [s, s]."""
    if causal:
        if s != t:
            raise ValueError(f"causal attention with s={s} != t={t} is not counted here")
        return s * (s + 1) // 2
    return s * t


def forward(bh: int, s: int, t: int, d: int, causal: bool) -> Tuple[float, float]:
    """(FLOPs, bytes) of one forward call: q, k, v read; o and lse written."""
    flops = 2 * 2 * d * products(s, t, causal) * bh
    nbytes = bh * ((s + 2 * t) * d * BF16 + s * d * BF16 + s * F32)
    return float(flops), float(nbytes)


def backward(bh: int, s: int, t: int, d: int, causal: bool) -> Tuple[float, float]:
    """(FLOPs, bytes) of one backward (the dq and the dk/dv calls together):
    q, k, v, do, lse and delta read; dq, dk, dv written."""
    flops = 5 * 2 * d * products(s, t, causal) * bh
    nbytes = bh * ((2 * s + 2 * t) * d * BF16 + 2 * s * F32 + (s + 2 * t) * d * BF16)
    return float(flops), float(nbytes)


def bound_s(work: Tuple[float, float], peaks) -> float:
    """The least time the chip could take: the larger of the FLOPs over the
    bf16 peak and the bytes over the HBM bandwidth."""
    flops, nbytes = work
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
