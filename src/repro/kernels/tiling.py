"""Block-shape rules Mosaic enforces on TPU BlockSpecs.

The last two dims of every block must either equal the array's own dims or be
multiples of the hardware tile: 128 lanes, and 8 sublanes for 32-bit types
(16 for 16-bit, 32 for 8-bit, which pack along sublanes).  ``tile`` picks a
legal block for one dim and says how far to pad the dim when no aligned
divisor exists -- a block is never shrunk to an unaligned divisor.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

LANE = 128


def sublane(dtype) -> int:
    """Sublane tile for ``dtype``: 8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def tile(n: int, pref: int, align: int, *, whole: bool = True) -> Tuple[int, int]:
    """``(block, padded_n)`` for a dim of size ``n``.

    The whole dim when it fits in ``pref`` and ``whole`` says the block may
    span the array's entire dim (always legal then); else the largest
    multiple of ``align`` that divides ``n`` and is at most ``pref``; else
    ``n`` padded up to a multiple of ``align`` (one block, if that fits in
    ``pref``) or of the largest aligned block at most ``pref``.  Pass
    ``whole=False`` for a dim that is only part of the array's dim (one half
    of a paired dim), where only aligned blocks are legal."""
    if whole and n <= pref:
        return n, n
    top = max(align, pref // align * align)
    for b in range(min(top, n // align * align), 0, -align):
        if n % b == 0:
            return b, n
    if round_up(n, align) <= top:
        return round_up(n, align), round_up(n, align)
    return top, round_up(n, top)
