"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (this container is CPU-only; the TPU is
the TARGET) -- interpret mode executes the kernel body for correctness while
``interpret=False`` emits the real Mosaic TPU kernel on hardware.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.coalesce_pair import coalesce_pair as _coalesce_pair
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.flash_attention import flash_attention_with_vjp as _flash_attention_vjp
from repro.kernels.interp_axpy import interp_axpy as _interp_axpy
from repro.kernels.paged_attention import paged_attention_decode as _paged_attention_decode


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, scale=None, block_q=128, block_k=128,
                    interpret=None):
    interp = (not _on_tpu()) if interpret is None else interpret
    return _flash_attention(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k, interpret=interp)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret"))
def flash_attention_vjp(q, k, v, *, causal=True, scale=None, block_q=128,
                        block_k=128, interpret=None):
    """Differentiable variant: Pallas forward and backward kernels."""
    interp = (not _on_tpu()) if interpret is None else interpret
    return _flash_attention_vjp(q, k, v, causal=causal, scale=scale,
                                block_q=block_q, block_k=block_k, interpret=interp)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_decode(q, k_pages, v_pages, block_tables, lengths, *,
                           scale=None, interpret=None):
    """Decode attention through per-sequence block tables (paged KV serving)."""
    interp = (not _on_tpu()) if interpret is None else interpret
    return _paged_attention_decode(q, k_pages, v_pages, block_tables, lengths,
                                   scale=scale, interpret=interp)


@functools.partial(jax.jit, static_argnames=("axis", "w0", "block", "interpret"))
def coalesce_pair(w, *, axis, w0=0.5, block=512, interpret=None):
    interp = (not _on_tpu()) if interpret is None else interpret
    return _coalesce_pair(w, axis=axis, w0=w0, block=block, interpret=interp)


@functools.partial(jax.jit, static_argnames=("alpha", "block", "interpret"))
def interp_axpy(a, b, alpha, *, block=1024, interpret=None):
    interp = (not _on_tpu()) if interpret is None else interpret
    return _interp_axpy(a, b, alpha, block=block, interpret=interp)
