"""Width-coalescing kernel (Pallas TPU): the paper's averaging F applied to a
weight matrix as a single fused pass.

For the "stack" variant F_out = [I/2; I/2] the column ("out"-role) projection
is  Y[:, j] = w0 * (W[:, j] + W[:, j + m])  and the row ("in"-role) projection
(F_in, weight 1.0 after the paper's normalization) is
Y[i, :] = w0 * (W[i, :] + W[i + n2, :]).

Instead of materializing F and running a [n x m] matmul (the naive path -- and
the ref.py oracle), the kernel reads the two paired tiles of W via two
BlockSpec views of the same array and writes one fused output tile: one pass
over HBM, no F matrix, no MXU occupancy.  De-coalescing's T_out duplication is
a gather (no kernel needed); T_in halves are this same kernel with w0=0.5.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANE, sublane, tile


def _pair_kernel(a_ref, b_ref, o_ref, *, w0: float):
    o_ref[...] = (w0 * (a_ref[...].astype(jnp.float32)
                        + b_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


def _pad_halves(w: jax.Array, axis: int, half: int, half_p: int) -> jax.Array:
    """Pad each half of ``axis`` from ``half`` to ``half_p`` so pair (i, i +
    half) stays at block offset ``half_p // block``."""
    a = jax.lax.slice_in_dim(w, 0, half, axis=axis)
    b = jax.lax.slice_in_dim(w, half, 2 * half, axis=axis)
    pad = [(0, 0), (0, 0)]
    pad[axis] = (0, half_p - half)
    return jnp.concatenate([jnp.pad(a, pad), jnp.pad(b, pad)], axis=axis)


def coalesce_pair(
    w: jax.Array,  # [n, c] (axis=0) or [r, n] (axis=1); n even
    *,
    axis: int,
    w0: float = 0.5,
    block: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Merge index pairs (i, i + n/2) along ``axis`` with weight ``w0``.

    Blocks are at most ``block`` per dim and Mosaic-legal (``tiling.tile``):
    a dim with no aligned divisor is padded -- the paired dim half by half,
    the other dim at its end -- and the output sliced back."""
    if w.ndim != 2:
        raise ValueError("coalesce_pair expects a 2D weight (fold other dims first)")
    n = w.shape[axis]
    if n % 2:
        raise ValueError(f"axis {axis} size {n} must be even")
    half = n // 2
    other = w.shape[1 - axis]
    align = (sublane(w.dtype), LANE)
    bp, half_p = tile(half, block, align[axis], whole=False)
    bo, other_p = tile(other, block, align[1 - axis])
    if half_p != half:
        w = _pad_halves(w, axis, half, half_p)
    if other_p != other:
        pad = [(0, 0), (0, 0)]
        pad[1 - axis] = (0, other_p - other)
        w = jnp.pad(w, pad)
    off = half_p // bp
    if axis == 0:
        blk = (bp, bo)
        grid = (half_p // bp, other_p // bo)
        b_map = lambda i, j: (i + off, j)
        out_shape = (half_p, other_p)
    else:
        blk = (bo, bp)
        grid = (other_p // bo, half_p // bp)
        b_map = lambda i, j: (i, j + off)
        out_shape = (other_p, half_p)
    out = pl.pallas_call(
        functools.partial(_pair_kernel, w0=w0),
        grid=grid,
        in_specs=[pl.BlockSpec(blk, lambda i, j: (i, j)), pl.BlockSpec(blk, b_map)],
        out_specs=pl.BlockSpec(blk, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(out_shape, w.dtype),
        interpret=interpret,
    )(w, w)
    if axis == 0:
        return out[:half, :other]
    return out[:other, :half]
