"""Interpolation kernel (Pallas TPU): the paper's Eq. 13
``out = (1 - alpha) * a + alpha * b`` fused over parameter tiles.

Memory-bound by construction (reads a, b once, writes out once); the fused
form avoids the two-pass scale+add XLA can emit for mixed-dtype trees at
level-transition time on 100B+ parameter models.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANE, sublane, tile

ROWS = 256  # sublane rows per block: 1 MiB of f32 at 1024 lanes


def _axpy_kernel(a_ref, b_ref, o_ref, *, alpha: float):
    af = a_ref[...].astype(jnp.float32)
    bf = b_ref[...].astype(jnp.float32)
    o_ref[...] = ((1.0 - alpha) * af + alpha * bf).astype(o_ref.dtype)


def interp_axpy(a: jax.Array, b: jax.Array, alpha: float, *,
                block: int = 1024, interpret: bool = False) -> jax.Array:
    """Tiled (1-alpha)*a + alpha*b over a parameter tensor viewed as
    ``[rows, block]`` (``block`` lanes, a multiple of 128), ``ROWS`` rows
    per grid step; the flat tail is zero-padded to whole rows."""
    if a.shape != b.shape:
        raise ValueError(f"shape mismatch {a.shape} vs {b.shape}")
    if block % LANE:
        raise ValueError(f"block={block} must be a multiple of {LANE} lanes")
    orig_shape = a.shape
    n = a.size
    br, rows = tile(-(-n // block), ROWS, sublane(a.dtype))
    pad = rows * block - n
    af = jnp.pad(a.reshape(-1), (0, pad)).reshape(rows, block)
    bf = jnp.pad(b.reshape(-1), (0, pad)).reshape(rows, block)
    spec = pl.BlockSpec((br, block), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_axpy_kernel, alpha=alpha),
        grid=(rows // br,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, block), a.dtype),
        interpret=interpret,
    )(af, bf)
    return out.reshape(-1)[:n].reshape(orig_shape)
