"""Paged-attention decode kernel (Pallas TPU): block-table K/V gather.

Serving keeps each sequence's KV cache as a list of fixed-size *pages* drawn
from a shared pool instead of one dense ``[batch, max_seq, ...]`` strip
(vLLM/flashinfer block-table layout).  Decode attention then reads K/V
*through* the block table, so per-step cost scales with the number of pages a
sequence actually occupies -- not with the server-wide ``max_seq``.

Pool layout ``[n_pages, kv_heads, page_size, head_dim]``: one (page, head)
block is a ``[page_size, head_dim]`` tile whose two dims are the array's own
last two dims, which Mosaic accepts for any page size and head width.

The kernel uses ``PrefetchScalarGridSpec``: the block table and per-sequence
lengths are scalar-prefetched so the K/V BlockSpec index maps can chase page
ids at grid-issue time (``k_pages[bt[b, m]]`` is a DMA program, not a gather
op).  Grid is ``(batch, kv_head, page)`` with the page axis innermost and
sequential; fp32 online-softmax state (m, l, acc) for the G query heads of
one kv head lives in VMEM scratch across pages, exactly like the flash
forward kernel in ``flash_attention.py`` (row statistics replicated across
one lane tile).  Pages past ``ceil(len/P)`` are skipped with ``pl.when`` --
no MXU issue for table padding.  Masked scores are the finite ``NEG_INF``:
page 0 of a live row always holds token 0, so the running max is finite from
the first computed page and masked probabilities are exactly 0.

A sequence of length 0 (an idle decode slot) produces an all-zero output row;
the XLA reference (``ref.paged_attention_ref``) pins the same convention so
backends agree bit-for-bit on masked rows.

Validated in interpret mode against the gather reference and against dense
attention over a contiguously reassembled cache (tests/test_kernels.py,
tests/test_dispatch.py), and compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF, STAT_LANES


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *,
                         scale: float, page_size: int, n_tables: int):
    b = pl.program_id(0)
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [P, D]
        v = v_ref[0, 0].astype(jnp.float32)  # [P, Dv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tp = m * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tp < length, s, NEG_INF)
        m_prev = m_scr[...]  # [G, STAT_LANES], lanes equal
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = corr[:, :1] * acc_scr[...] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    # skip pages holding no valid token (table padding / short sequences)
    pl.when(m * page_size < length)(_compute)

    @pl.when(m == n_tables - 1)
    def _out():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)


def paged_attention_decode(
    q: jax.Array,             # [B, KH, G, D]  one query token per sequence
    k_pages: jax.Array,       # [N, KH, P, D]  shared page pool
    v_pages: jax.Array,       # [N, KH, P, Dv]
    block_tables: jax.Array,  # [B, M] int32 page ids (padding entries: 0)
    lengths: jax.Array,       # [B] int32 valid tokens per sequence
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention through a block table; returns [B, KH, G, Dv]."""
    B, KH, G, D = q.shape
    N, _, P, Dv = v_pages.shape
    M = block_tables.shape[1]
    scale = D ** -0.5 if scale is None else scale

    kern = functools.partial(_paged_decode_kernel, scale=float(scale),
                             page_size=P, n_tables=M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KH, M),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, m, bt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, D), lambda b, h, m, bt, ln: (bt[b, m], h, 0, 0)),
            pl.BlockSpec((1, 1, P, Dv), lambda b, h, m, bt, ln: (bt[b, m], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv), lambda b, h, m, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, STAT_LANES), jnp.float32),
            pltpu.VMEM((G, STAT_LANES), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, Dv), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pages, v_pages)
