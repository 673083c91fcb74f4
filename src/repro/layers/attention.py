"""Attention: GQA (opt. qk-norm), MLA (DeepSeek-V3, absorbed decode), cross-attn.

Three core computations:
  * ``plain_attention``    - materialized scores (decode / small seq)
  * ``blockwise_attention``- online-softmax scan over KV blocks (O(S) memory;
                             rectangular work, also for non-causal)
  * ``pairs_attention``    - causal, FLOP-exact: scans only the lower-triangular
                             (q-block, k-block) pairs.  Used for long prefill and
                             available for training (perf lever, see EXPERIMENTS).

Train/prefill attention past 128 tokens takes the Pallas flash kernels
(repro.kernels.dispatch, forward AND backward through a custom VJP) when the
shapes allow and ``flash_attention`` resolves to a Pallas backend: on TPU
unless ``xla`` is pinned (``_flash_backend``), in tiles of the whole sequence
up to ``FLASH_TILE`` tokens; sequence lengths the blocks do not divide are
padded.  The XLA flash recipe below remains for the shapes the
kernel cannot express (causal S != T, padded non-causal keys) and off TPU.
Both flash paths assume query positions 0..S-1 (train/prefill); decode uses
plain attention.

Short self-attention without a cache (``S <= 128``, rotary positions) takes
``attention_tile`` instead -- the rotary, scores, softmax and their backward
in one Pallas kernel pair -- whenever its backend resolves to a Pallas kernel
(``_tile_backend``).

All attention math runs in fp32 softmax with bf16 matmul inputs (TPU MXU style).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed import shard_l
from repro.kernels import attention_tile as tile_lib
from repro.kernels import dispatch as kdispatch
from repro.kernels.flash_attention import flash_tiles
from repro.layers.basic import apply_rope, rms_norm
from repro.param import Spec

NEG_INF = -1e30


def paged_write(pages: jax.Array, new: jax.Array, positions: jax.Array,
                block_tables: jax.Array, seq_axis: int = 1) -> jax.Array:
    """Scatter ``new`` [B,S,...] into ``pages`` at absolute ``positions``
    [B,S] routed through per-sequence ``block_tables`` [B,M].  The pool is
    [N,P,...] (``seq_axis=1``), or head-major [N,KH,P,D] (``seq_axis=2``,
    the GQA K/V pool) with ``new`` [B,S,KH,D].

    Touches only the pages the written tokens land in -- admission/decode
    cost scales with the request, not with the pool.  Position -1 marks a
    padding slot (bucketed extend steps left-pad); its write is routed to
    page 0, the pool's reserved null page that no request ever owns.
    """
    P = pages.shape[seq_axis]
    valid = positions >= 0
    pos = jnp.maximum(positions, 0)
    page_ix = jnp.minimum(pos // P, block_tables.shape[1] - 1)
    pid = jnp.take_along_axis(block_tables, page_ix, axis=1)
    pid = jnp.where(valid, pid, 0)
    off = jnp.where(valid, pos % P, 0)
    # advanced indices split by slices put the [B,S] dims first: the target
    # of a head-major write is [B,S,KH,D], the layout of ``new``
    idx = (pid,) + (slice(None),) * (seq_axis - 1) + (off,)
    return pages.at[idx].set(new.astype(pages.dtype))


def seq_masked_write(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write ``new`` [B,1,...] into ``cache`` [B,T,...] at per-example ``pos``.

    A dynamic_update_slice at a data-dependent index on the SEQUENCE-SHARDED
    cache axis forces GSPMD to all-gather the whole cache every decode step
    (the baseline deepseek-v3 decode_32k bottleneck: 161 GB/step of AG --
    EXPERIMENTS.md §Perf).  A masked select is elementwise, so every shard
    updates (or not) its own slice locally: zero collectives, one local
    read+write pass over the cache shard.
    """
    T = cache.shape[1]
    mask = jnp.arange(T)[None, :] == pos[:, None]  # [B,T]
    mask = mask.reshape(mask.shape + (1,) * (cache.ndim - 2))
    return jnp.where(mask, new.astype(cache.dtype), cache)


# ---------------------------------------------------------------------------
# core attention computations


def _mask(qp: jax.Array, tp: jax.Array, causal: bool) -> jax.Array:
    """qp: [B,S] query positions, tp: [T] key positions -> [B,S,T] bool."""
    if not causal:
        return jnp.ones(qp.shape + (tp.shape[0],), bool)
    return tp[None, None, :] <= qp[:, :, None]


def plain_attention(q, k, v, *, causal: bool, scale: float, q_positions=None) -> jax.Array:
    """q: [B,S,KH,G,Dq], k: [B,T,KH,Dq], v: [B,T,KH,Dv] -> [B,S,KH,G,Dv]."""
    B, S, KH, G, Dq = q.shape
    T = k.shape[1]
    s = jnp.einsum("bskgd,btkd->bkgst", q, k, preferred_element_type=jnp.float32) * scale
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    m = _mask(q_positions, jnp.arange(T), causal)  # [B,S,T]
    s = jnp.where(m[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgst,btkv->bskgv", p.astype(v.dtype), v)


def blockwise_attention(q, k, v, *, causal: bool, scale: float, block_k: int,
                        q_positions=None) -> jax.Array:
    """Online-softmax over KV blocks (rectangular; works for any mask)."""
    B, S, KH, G, Dq = q.shape
    T = k.shape[1]
    bk = min(block_k, T)
    if T % bk:  # pad keys to a multiple of bk; padded keys are masked out
        pad = bk - T % bk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nT = k.shape[1]
    nb = nT // bk
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    kb = k.reshape(B, nb, bk, KH, Dq).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, bk, KH, -1).transpose(1, 0, 2, 3, 4)
    t0s = jnp.arange(nb) * bk

    qf = q.astype(jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kblk, vblk, t0 = xs
        s = jnp.einsum("bskgd,btkd->bskgt", qf, kblk.astype(jnp.float32)) * scale
        tp = t0 + jnp.arange(bk)
        valid = tp[None, None, :] < T
        if causal:
            valid = valid & (tp[None, None, :] <= q_positions[:, :, None])
        s = jnp.where(valid[:, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1)
        acc_new = corr[..., None] * acc + jnp.einsum(
            "bskgt,btkv->bskgv", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    Dv = v.shape[-1]
    init = (
        jnp.full((B, S, KH, G), NEG_INF, jnp.float32),
        jnp.zeros((B, S, KH, G), jnp.float32),
        jnp.zeros((B, S, KH, G, Dv), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (kb, vb, t0s))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def pairs_attention(q, k, v, *, scale: float, block: int) -> jax.Array:
    """Causal FLOP-exact attention: scan over lower-triangular block pairs.

    Requires S == T and S % block == 0 (configs guarantee it for train/prefill).
    """
    B, S, KH, G, Dq = q.shape
    T = k.shape[1]
    assert S == T and S % block == 0, (S, T, block)
    nq = S // block
    Dv = v.shape[-1]
    qc = q.reshape(B, nq, block, KH, G, Dq).astype(jnp.float32)
    kc = k.reshape(B, nq, block, KH, Dq)
    vc = v.reshape(B, nq, block, KH, Dv)
    qi = jnp.concatenate([jnp.full((i + 1,), i, jnp.int32) for i in range(nq)])
    ki = jnp.concatenate([jnp.arange(i + 1, dtype=jnp.int32) for i in range(nq)])

    pos_in_block = jnp.arange(block)

    def body(carry, xs):
        m, l, acc = carry  # m,l: [B,nq,block,KH,G]; acc: [...,Dv]
        i, j = xs
        qi_blk = jax.lax.dynamic_index_in_dim(qc, i, axis=1, keepdims=False)
        ki_blk = jax.lax.dynamic_index_in_dim(kc, j, axis=1, keepdims=False)
        vi_blk = jax.lax.dynamic_index_in_dim(vc, j, axis=1, keepdims=False)
        s = jnp.einsum("bskgd,btkd->bskgt", qi_blk, ki_blk.astype(jnp.float32)) * scale
        # mask only needed on the diagonal block (i == j)
        diag = (i == j)
        qp = i * block + pos_in_block
        tp = j * block + pos_in_block
        allow = jnp.where(diag, tp[None, :] <= qp[:, None], True)
        s = jnp.where(allow[None, :, None, None, :], s, -jnp.inf)
        m_i = jax.lax.dynamic_index_in_dim(m, i, axis=1, keepdims=False)
        l_i = jax.lax.dynamic_index_in_dim(l, i, axis=1, keepdims=False)
        a_i = jax.lax.dynamic_index_in_dim(acc, i, axis=1, keepdims=False)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.exp(m_i - m_new)
        l_new = corr * l_i + jnp.sum(p, axis=-1)
        a_new = corr[..., None] * a_i + jnp.einsum(
            "bskgt,btkv->bskgv", p, vi_blk.astype(jnp.float32))
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, axis=1)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, axis=1)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, i, axis=1)
        return (m, l, acc), None

    init = (
        jnp.full((B, nq, block, KH, G), NEG_INF, jnp.float32),
        jnp.zeros((B, nq, block, KH, G), jnp.float32),
        jnp.zeros((B, nq, block, KH, G, Dv), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (qi, ki))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S, KH, G, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# custom-VJP flash-style attention for the XLA path
#
# A plain differentiated blockwise/pairs scan stores (or carries cotangents
# for) O(S^2)-adjacent intermediates; the baseline dry-run measured 15-60 GB
# of per-device temp on every train_4k cell from exactly this (EXPERIMENTS.md
# §Perf iter.1).  The custom VJP saves only (q, k, v, out, lse) and recomputes
# probabilities per KV block in the backward -- the flash-attention recipe,
# expressed in jnp so it lowers for any backend (the Pallas kernel is the TPU
# runtime fast path; this is the same algorithm at the XLA level).


def _fa_fwd_scan(q, k, v, *, causal: bool, scale: float, block_k: int):
    """Returns (out [B,S,KH,G,Dv], lse [B,S,KH,G]).  Query positions are
    0..S-1 (train/prefill); decode uses plain attention."""
    B, S, KH, G, Dq = q.shape
    q_positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    T = k.shape[1]
    bk = min(block_k, T)
    pad = (-T) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = k.shape[1] // bk
    kb = k.reshape(B, nb, bk, KH, Dq).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, bk, KH, -1).transpose(1, 0, 2, 3, 4)
    qf = q.astype(jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kblk, vblk, t0 = xs
        s = jnp.einsum("bskgd,btkd->bskgt", qf, kblk.astype(jnp.float32)) * scale
        tp = t0 + jnp.arange(bk)
        valid = tp[None, None, :] < T
        if causal:
            valid = valid & (tp[None, None, :] <= q_positions[:, :, None])
        s = jnp.where(valid[:, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1)
        acc_new = corr[..., None] * acc + jnp.einsum(
            "bskgt,btkv->bskgv", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    Dv = v.shape[-1]
    init = (jnp.full((B, S, KH, G), NEG_INF, jnp.float32),
            jnp.zeros((B, S, KH, G), jnp.float32),
            jnp.zeros((B, S, KH, G, Dv), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, (kb, vb, jnp.arange(nb) * bk))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_xla(q, k, v, causal: bool, scale: float, block_k: int):
    out, _ = _fa_fwd_scan(q, k, v, causal=causal, scale=scale, block_k=block_k)
    return out


def _flash_xla_fwd(q, k, v, causal, scale, block_k):
    out, lse = _fa_fwd_scan(q, k, v, causal=causal, scale=scale, block_k=block_k)
    return out, (q, k, v, out, lse)


def _flash_xla_bwd(causal, scale, block_k, res, do):
    q, k, v, out, lse = res
    B, S, KH, G, Dq = q.shape
    q_positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    T = k.shape[1]
    Dv = v.shape[-1]
    bk = min(block_k, T)
    pad = (-T) % bk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    nb = kp.shape[1] // bk
    kb = kp.reshape(B, nb, bk, KH, Dq).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nb, bk, KH, Dv).transpose(1, 0, 2, 3, 4)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    # D_i = sum_v do*out  (rowwise correction term of the flash backward)
    Dterm = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # [B,S,KH,G]

    def body(dq_acc, xs):
        kblk, vblk, t0 = xs
        s = jnp.einsum("bskgd,btkd->bskgt", qf, kblk.astype(jnp.float32)) * scale
        tp = t0 + jnp.arange(bk)
        valid = tp[None, None, :] < T
        if causal:
            valid = valid & (tp[None, None, :] <= q_positions[:, :, None])
        s = jnp.where(valid[:, :, None, None, :], s, -jnp.inf)
        p = jnp.exp(s - lse[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)  # [B,S,KH,G,bk]
        dv_b = jnp.einsum("bskgt,bskgv->btkv", p, dof)
        dp = jnp.einsum("bskgv,btkv->bskgt", dof, vblk.astype(jnp.float32))
        ds = p * (dp - Dterm[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bskgt,btkd->bskgd", ds, kblk.astype(jnp.float32))
        dk_b = jnp.einsum("bskgt,bskgd->btkd", ds, qf)
        return dq_acc, (dk_b, dv_b)

    dq0 = jnp.zeros((B, S, KH, G, Dq), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(nb) * bk))
    dk = dk_b.transpose(1, 0, 2, 3, 4).reshape(B, nb * bk, KH, Dq)[:, :T]
    dv = dv_b.transpose(1, 0, 2, 3, 4).reshape(B, nb * bk, KH, Dv)[:, :T]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_xla.defvjp(_flash_xla_fwd, _flash_xla_bwd)


def _flash_pallas(q, k, v, *, causal: bool, scale: float, bq: int, bk: int,
                  backend: str) -> jax.Array:
    """Adapter from the layer layout [B,S,KH,G,D] to the kernel's [B,H,S,D].

    GQA KV is broadcast over the query groups BEFORE the custom-VJP boundary:
    the kernel then sees matched head counts, and the group-sum of dk/dv falls
    out of the broadcast's own VJP (no GQA logic inside the kernel).
    """
    B, S, KH, G, Dq = q.shape
    T = k.shape[1]
    Dv = v.shape[-1]
    qh = q.transpose(0, 2, 3, 1, 4).reshape(B, KH * G, S, Dq)
    kh = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (B, KH, G, T, Dq)).reshape(B, KH * G, T, Dq)
    vh = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (B, KH, G, T, Dv)).reshape(B, KH * G, T, Dv)
    out = kdispatch.get_impl("flash_attention", backend)(
        qh, kh, vh, causal=causal, scale=scale, block_q=bq, block_k=bk)
    return out.reshape(B, KH, G, S, Dv).transpose(0, 3, 1, 2, 4)


# The flash kernels' q and k tile: the whole sequence up to 1024 tokens.
# On a TPU v5e at GPT-Base's 1024 tokens (12 and 6 heads of 64), forward
# plus backward took 5.2 / 3.1 ms in 1024 x 1024 tiles, 6.1 / 3.5 in 512 x
# 512 and 18.9 / 9.6 in 128 x 128, against flash_xla's 11.4 / 5.4: a grid
# step costs more than the masked tiles a larger one cannot skip (PERF.md).
FLASH_TILE = 1024


def run_attention(q, k, v, cfg: ModelConfig, *, causal: bool, scale: float,
                  q_positions=None, decode: bool = False) -> jax.Array:
    """Attention of q ``[B,S,KH,G,D]`` over k, v ``[B,T,KH,D]``.

    Decode and short calls (``S <= 128`` or ``T <= attn_block_k``) keep
    ``plain_attention``; ``attn_impl="pairs"`` takes the causal pairs scan
    where it divides; ``attn_impl="plain"`` keeps ``plain_attention``.
    Otherwise the shapes decide (``_flash_backend``): a call the Pallas
    flash kernels can express runs them wherever ``flash_attention``
    resolves to a Pallas backend, and every other call takes ``flash_xla``,
    the same recipe at the XLA level."""
    S, T = q.shape[1], k.shape[1]
    impl = cfg.attn_impl
    if decode or S <= 128 or T <= cfg.attn_block_k:
        return plain_attention(q, k, v, causal=causal, scale=scale, q_positions=q_positions)
    if impl == "pairs" and causal and S == T and S % cfg.attn_block_k == 0:
        # FLOP-exact causal (lower-triangular block pairs); best for no-grad
        # prefill where the rectangular fwd would waste ~2x attention FLOPs.
        return pairs_attention(q, k, v, scale=scale, block=cfg.attn_block_k)
    if impl not in ("blockwise", "pallas", "pairs"):
        return plain_attention(q, k, v, causal=causal, scale=scale, q_positions=q_positions)
    backend = _flash_backend(cfg, S, T, causal=causal, dtype=q.dtype)
    if backend is not None:
        return _flash_pallas(q, k, v, causal=causal, scale=scale, bq=FLASH_TILE,
                             bk=FLASH_TILE, backend=backend)
    # the XLA flash recipe (the same algorithm), noted so that a run can tell
    # which implementation it traced
    kdispatch.note("flash_attention", "xla")
    return flash_xla(q, k, v, causal, scale, cfg.attn_block_k)


def _flash_backend(cfg: ModelConfig, S: int, T: int, *, causal: bool,
                   dtype) -> Optional[str]:
    """The Pallas backend of ``flash_attention`` when this call takes the
    kernels, else None.  It does when the kernels can express the call --
    causal with ``S == T`` (queries and keys both at positions 0..S-1), or
    non-causal keys that the blocks tile without a pad -- and the backend
    resolves to a Pallas kernel: on TPU unless the config or the
    environment pins ``xla``; off TPU only when pinned to
    ``pallas``/``pallas-interpret``, or under ``attn_impl="pallas"``, which
    still prefers the kernels (the interpreter there)."""
    expressible = (S == T if causal else
                   flash_tiles(S, T, FLASH_TILE, FLASH_TILE, causal, dtype) is not None)
    if not expressible:
        return None
    backend = kdispatch.resolve_backend(
        "flash_attention", cfg.kernel_backend or None,
        default="pallas" if cfg.attn_impl == "pallas" else None)
    return None if backend == "xla" else backend


def _tile_backend(cfg: ModelConfig, S: int, *, use_rope: bool,
                  cache: Optional[Dict]) -> Optional[str]:
    """The Pallas backend of ``attention_tile`` when this self-attention call
    takes it, else None.  It does when the call can be seen to fit one tile:
    no cache (training, eval and a cold prefill: the keys are the queries'
    own S tokens, at positions from 0), rotary positions, ``S <= 128`` and
    a multiple of 8, heads whose width divides 128 lanes and that fill whole
    128-lane tiles, and a backend that resolves to a Pallas kernel (on TPU unless the config
    or the environment pins ``xla``; off TPU only when pinned to
    ``pallas``/``pallas-interpret``)."""
    if (cache is not None or not use_rope
            or not tile_lib.fits(S, cfg.n_heads, cfg.resolved_head_dim)):
        return None
    backend = kdispatch.resolve_backend("attention_tile",
                                        cfg.kernel_backend or None)
    return None if backend == "xla" else backend


def _gqa_tile(p: Dict, x: jax.Array, cfg: ModelConfig, *, positions: jax.Array,
              causal: bool, backend: str) -> jax.Array:
    """Self-attention of one short sequence through ``attention_tile``.

    The projections write the heads side by side, ``[B,S,H*D]``, which is
    the kernel's layout, so nothing is transposed around it; the rotary runs
    inside the kernel.  (Projecting to ``[B,S,H,D]`` as ``gqa_apply`` does
    and reshaping puts layout copies around the kernels and float32
    ``[B,S,H,D]`` arrays back in the compiled v5e train step.)  GQA K/V
    are repeated over the query groups first, so the group-sum of dk/dv
    falls out of the repeat's own VJP."""
    B, S, E = x.shape
    H, KH, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    cdt = cfg.compute_dtype

    def proj(name: str, n: int, norm: Optional[str]) -> jax.Array:
        y = jnp.einsum("bse,ef->bsf", x, p["w" + name].astype(cdt).reshape(E, n * D))
        if cfg.use_bias:
            y = y + p["b" + name].astype(cdt).reshape(n * D)
        if norm and cfg.qk_norm:
            y = rms_norm(y.reshape(B, S, n, D), p[norm], cfg.norm_eps)
        if n != H:
            y = jnp.repeat(y.reshape(B, S, n, D), H // n, axis=2)
        return y.reshape(B, S, H * D)

    out = kdispatch.get_impl("attention_tile", backend)(
        proj("q", H, "q_norm"), proj("k", KH, "k_norm"), proj("v", KH, None),
        positions, head_dim=D, causal=causal, scale=D ** -0.5,
        theta=cfg.rope_theta)
    y = jnp.einsum("bsf,fe->bse", out, p["wo"].astype(cdt).reshape(H * D, E))
    if cfg.use_bias:
        y = y + p["bo"].astype(cdt)
    return shard_l(y, ("batch", "seq", "act_embed"))


# ---------------------------------------------------------------------------
# GQA layer


def gqa_specs(cfg: ModelConfig) -> Dict[str, Spec]:
    E, H, KH, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    s = {
        "wq": Spec((E, H, D), ("embed", "heads", "head_dim"), ("in", "out", "-"), init="fan_in"),
        "wk": Spec((E, KH, D), ("embed", "kv_heads", "head_dim"), ("in", "out", "-"), init="fan_in"),
        "wv": Spec((E, KH, D), ("embed", "kv_heads", "head_dim"), ("in", "out", "-"), init="fan_in"),
        "wo": Spec((H, D, E), ("heads", "head_dim", "embed"), ("in", "-", "out"), init="fan_in"),
    }
    if cfg.qk_norm:
        s["q_norm"] = Spec((D,), ("head_dim",), ("-",), init="ones")
        s["k_norm"] = Spec((D,), ("head_dim",), ("-",), init="ones")
    if cfg.use_bias:
        s["bq"] = Spec((H, D), ("heads", "head_dim"), ("out", "-"), init="zeros")
        s["bk"] = Spec((KH, D), ("kv_heads", "head_dim"), ("out", "-"), init="zeros")
        s["bv"] = Spec((KH, D), ("kv_heads", "head_dim"), ("out", "-"), init="zeros")
        s["bo"] = Spec((E,), ("embed",), ("out",), init="zeros")
    return s


def gqa_cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, Spec]:
    KH, D = cfg.n_kv_heads, cfg.resolved_head_dim
    ax = ("batch", "cache_seq", "cache_kv_heads", "head_dim")
    dt = cfg.compute_dtype
    return {
        "k": Spec((batch, max_seq, KH, D), ax, init="zeros", dtype=dt),
        "v": Spec((batch, max_seq, KH, D), ax, init="zeros", dtype=dt),
    }


def gqa_paged_cache_specs(cfg: ModelConfig, n_pages: int, page_size: int) -> Dict[str, Spec]:
    """Page-pool K/V leaves: ``[n_pages, KH, page_size, D]`` shared across all
    sequences (block tables route each sequence to its pages).  Head-major,
    so one (page, head) is a ``[page_size, D]`` tile for the decode kernel."""
    KH, D = cfg.n_kv_heads, cfg.resolved_head_dim
    ax = ("pages", "cache_kv_heads", "page_seq", "head_dim")
    dt = cfg.compute_dtype
    return {
        "k": Spec((n_pages, KH, page_size, D), ax, init="zeros", dtype=dt),
        "v": Spec((n_pages, KH, page_size, D), ax, init="zeros", dtype=dt),
    }


def _paged_gqa_attention(qg, cache_k, cache_v, cfg: ModelConfig, *,
                         positions: jax.Array, block_tables: jax.Array,
                         scale: float) -> jax.Array:
    """qg: [B,S,KH,G,D] against paged K/V [N,KH,P,D] -> [B,S,KH,G,D].

    S == 1 (decode) dispatches to the registered ``paged_attention_decode``
    op; S > 1 (prefix-extend prefill) gathers the table's pages and runs the
    plain masked attention -- either way, work scales with M*P (the pages the
    batch actually spans), not with the server-wide max_seq.
    """
    B, S = qg.shape[:2]
    _, KH, P, D = cache_k.shape
    M = block_tables.shape[1]
    if S == 1:
        lengths = positions[:, -1] + 1  # the just-written token is attendable
        backend = kdispatch.resolve_backend(
            "paged_attention_decode", cfg.kernel_backend or None,
            default="pallas" if cfg.attn_impl == "pallas" else None)
        out = kdispatch.get_impl("paged_attention_decode", backend)(
            qg[:, 0], cache_k, cache_v, block_tables, lengths, scale=scale)
        return out[:, None]
    k = cache_k[block_tables].transpose(0, 1, 3, 2, 4).reshape(B, M * P, KH, D)
    v = cache_v[block_tables].transpose(0, 1, 3, 2, 4).reshape(
        B, M * P, KH, cache_v.shape[-1])
    return plain_attention(qg, k, v, causal=True, scale=scale,
                           q_positions=positions)


def gqa_apply(
    p: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,  # [B,S] absolute positions (rope + causal mask)
    causal: bool,
    use_rope: bool = True,
    cache: Optional[Dict] = None,
    block_tables: Optional[jax.Array] = None,  # [B,M]: cache is paged
) -> Tuple[jax.Array, Optional[Dict]]:
    B, S, E = x.shape
    tile = _tile_backend(cfg, S, use_rope=use_rope, cache=cache)
    if tile is not None:
        return _gqa_tile(p, x, cfg, positions=positions, causal=causal,
                         backend=tile), None
    H, KH, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    cdt = cfg.compute_dtype
    q = jnp.einsum("bse,ehd->bshd", x, p["wq"].astype(cdt))
    k = jnp.einsum("bse,ehd->bshd", x, p["wk"].astype(cdt))
    v = jnp.einsum("bse,ehd->bshd", x, p["wv"].astype(cdt))
    if cfg.use_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # context parallelism: when heads don't divide the model axis (qwen3-14b:
    # 40 heads, whisper: 20), shard the query/output SEQUENCE instead -- each
    # shard attends to the full (replicated) K/V; no attention collectives.
    q_seq_ax = "attn_seq" if (cfg.attn_seq_shard and cache is None) else "seq"
    q = shard_l(q, ("batch", q_seq_ax, "act_heads", "head_dim"))
    k = shard_l(k, ("batch", "seq", "act_kv_heads", "head_dim"))
    v = shard_l(v, ("batch", "seq", "act_kv_heads", "head_dim"))

    new_cache = None
    if cache is not None and block_tables is not None:
        # paged decode/extend: write the new tokens' K/V into their pages,
        # then attend through the block table (single-host serving path --
        # the pool is not mesh-sharded, so no shard_l constraints here)
        ck = paged_write(cache["k"], k, positions, block_tables, seq_axis=2)
        cv = paged_write(cache["v"], v, positions, block_tables, seq_axis=2)
        qg = q.reshape(B, S, KH, H // KH, D)
        out = _paged_gqa_attention(qg, ck, cv, cfg, positions=positions,
                                   block_tables=block_tables, scale=D ** -0.5)
        out = out.reshape(B, S, H, D)
        y = jnp.einsum("bshd,hde->bse", out, p["wo"].astype(cdt))
        if cfg.use_bias:
            y = y + p["bo"].astype(cdt)
        return y, {"k": ck, "v": cv}
    if cache is not None:
        pos0 = positions[:, 0]  # [B] write offsets
        ck = seq_masked_write(cache["k"], k, pos0)
        cv = seq_masked_write(cache["v"], v, pos0)
        ck = shard_l(ck, ("batch", "cache_seq", "cache_kv_heads", "head_dim"))
        cv = shard_l(cv, ("batch", "cache_seq", "cache_kv_heads", "head_dim"))
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv

    qg = q.reshape(B, S, KH, H // KH, D)
    scale = D ** -0.5
    out = run_attention(qg, k, v, cfg, causal=causal, scale=scale,
                        q_positions=positions, decode=cache is not None)
    out = out.reshape(B, S, H, D)
    out = shard_l(out, ("batch", q_seq_ax, "act_heads", "head_dim"))
    y = jnp.einsum("bshd,hde->bse", out, p["wo"].astype(cdt))
    if cfg.use_bias:
        y = y + p["bo"].astype(cdt)
    y = shard_l(y, ("batch", "seq", "act_embed"))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA layer (DeepSeek-V3)


def mla_specs(cfg: ModelConfig) -> Dict[str, Spec]:
    E, H = cfg.d_model, cfg.n_heads
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq_a": Spec((E, ql), ("embed", "q_lora"), ("in", "out"), init="fan_in"),
        "q_norm": Spec((ql,), ("q_lora",), ("out",), init="ones"),
        "wq_b": Spec((ql, H, nope + rope_d), ("q_lora", "heads", "head_dim"),
                     ("in", "out", "-"), init="fan_in"),
        "wkv_a": Spec((E, kl), ("embed", "kv_lora"), ("in", "out"), init="fan_in"),
        "wk_rope": Spec((E, rope_d), ("embed", "rope_dim"), ("in", "-"), init="fan_in"),
        "kv_norm": Spec((kl,), ("kv_lora",), ("out",), init="ones"),
        "wkv_b": Spec((kl, H, nope + vd), ("kv_lora", "heads", "head_dim"),
                      ("in", "out", "-"), init="fan_in"),
        "wo": Spec((H, vd, E), ("heads", "v_head_dim", "embed"), ("in", "-", "out"),
                   init="fan_in"),
    }


def mla_cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, Spec]:
    dt = cfg.compute_dtype
    return {
        "ckv": Spec((batch, max_seq, cfg.kv_lora_rank), ("batch", "cache_seq", "kv_lora"),
                    init="zeros", dtype=dt),
        "kpe": Spec((batch, max_seq, cfg.qk_rope_head_dim), ("batch", "cache_seq", "rope_dim"),
                    init="zeros", dtype=dt),
    }


def mla_paged_cache_specs(cfg: ModelConfig, n_pages: int, page_size: int) -> Dict[str, Spec]:
    """Paged compressed-latent cache: the MLA analogue of the K/V page pool
    (the latent + rope strips are what absorbed decode actually reads)."""
    dt = cfg.compute_dtype
    return {
        "ckv": Spec((n_pages, page_size, cfg.kv_lora_rank),
                    ("pages", "page_seq", "kv_lora"), init="zeros", dtype=dt),
        "kpe": Spec((n_pages, page_size, cfg.qk_rope_head_dim),
                    ("pages", "page_seq", "rope_dim"), init="zeros", dtype=dt),
    }


def mla_apply(
    p: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    causal: bool = True,
    cache: Optional[Dict] = None,
    block_tables: Optional[jax.Array] = None,  # [B,M]: cache is paged
) -> Tuple[jax.Array, Optional[Dict]]:
    B, S, E = x.shape
    H = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cdt = cfg.compute_dtype
    scale = (nope + rope_d) ** -0.5

    cq = rms_norm(jnp.einsum("bse,eq->bsq", x, p["wq_a"].astype(cdt)), p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsq,qhd->bshd", cq, p["wq_b"].astype(cdt))
    qn, qp = q[..., :nope], q[..., nope:]
    qp = apply_rope(qp, positions, cfg.rope_theta)
    # decode: the model axis belongs to the seq-sharded latent cache; sharding
    # q by heads too would force a 268MB/layer cache all-gather (the baseline
    # deepseek decode_32k bottleneck -- EXPERIMENTS.md §Perf).  Queries are
    # tiny; replicate them over model and let the scores/ctx contractions
    # reduce over the sharded cache sequence instead.
    head_ax = "seq" if cache is not None else "act_heads"
    q = shard_l(jnp.concatenate([qn, qp], -1), ("batch", "seq", head_ax, "head_dim"))

    ckv = rms_norm(jnp.einsum("bse,el->bsl", x, p["wkv_a"].astype(cdt)), p["kv_norm"], cfg.norm_eps)
    kpe = apply_rope(jnp.einsum("bse,er->bsr", x, p["wk_rope"].astype(cdt))[:, :, None, :],
                     positions, cfg.rope_theta)[:, :, 0, :]

    if cache is None:
        # training / prefill: expand per-head K,V and run standard attention
        kv = jnp.einsum("bsl,lhd->bshd", ckv, p["wkv_b"].astype(cdt))
        kn, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate([kn, jnp.broadcast_to(kpe[:, :, None, :], (B, S, H, rope_d))], -1)
        k = shard_l(k, ("batch", "seq", "act_heads", "head_dim"))
        v = shard_l(v, ("batch", "seq", "act_heads", "head_dim"))
        qg = q[:, :, :, None, :]  # KH == H, G == 1
        out = run_attention(qg, k, v, cfg, causal=causal, scale=scale, q_positions=positions)
        out = out[:, :, :, 0, :]
        new_cache = None
    else:
        # absorbed decode: score and combine in the compressed latent space
        if block_tables is not None:
            # paged: latent/rope strips live in a shared page pool; reassemble
            # this batch's rows by gathering through the block table.  tp below
            # is then the logical position (table slot i covers [i*P,(i+1)*P)),
            # so the existing position mask also hides table padding (page 0).
            # Single-host serving path -- no shard_l on the pool.
            cc = paged_write(cache["ckv"], ckv, positions, block_tables)
            ck = paged_write(cache["kpe"], kpe, positions, block_tables)
            new_cache = {"ckv": cc, "kpe": ck}
            M, P = block_tables.shape[1], cc.shape[1]
            cc = cc[block_tables].reshape(B, M * P, cc.shape[-1])
            ck = ck[block_tables].reshape(B, M * P, ck.shape[-1])
        else:
            pos0 = positions[:, 0]
            cc = seq_masked_write(cache["ckv"], ckv, pos0)
            ck = seq_masked_write(cache["kpe"], kpe, pos0)
            cc = shard_l(cc, ("batch", "cache_seq", "kv_lora"))
            ck = shard_l(ck, ("batch", "cache_seq", "rope_dim"))
            new_cache = {"ckv": cc, "kpe": ck}
        wk_b = p["wkv_b"].astype(cdt)[..., :nope]  # [kl,H,nope]
        wv_b = p["wkv_b"].astype(cdt)[..., nope:]  # [kl,H,vd]
        q_eff = jnp.einsum("bshn,lhn->bshl", qn, wk_b)
        s = jnp.einsum("bshl,btl->bhst", q_eff.astype(jnp.float32), cc.astype(jnp.float32))
        s = s + jnp.einsum("bshr,btr->bhst", qp.astype(jnp.float32), ck.astype(jnp.float32))
        s = s * scale
        tp = jnp.arange(cc.shape[1])
        mask = tp[None, None, :] <= positions[:, :, None]  # [B,S,T]
        s = jnp.where(mask[:, None, :, :], s, NEG_INF)
        prob = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhst,btl->bshl", prob.astype(cdt), cc)
        out = jnp.einsum("bshl,lhv->bshv", ctx, wv_b)

    y = jnp.einsum("bshv,hve->bse", out, p["wo"].astype(cdt))
    y = shard_l(y, ("batch", "seq", "act_embed"))
    return y, new_cache


# ---------------------------------------------------------------------------
# cross attention (VLM image layers, enc-dec decoder)


def cross_attn_specs(cfg: ModelConfig, kv_axis: str = "embed", kv_dim: int = 0) -> Dict[str, Spec]:
    E, H, KH, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    kvd = kv_dim or E
    kv_role = "in" if kv_axis == "embed" else "-"
    return {
        "wq": Spec((E, H, D), ("embed", "heads", "head_dim"), ("in", "out", "-"), init="fan_in"),
        "wk": Spec((kvd, KH, D), (kv_axis, "kv_heads", "head_dim"), (kv_role, "out", "-"), init="fan_in"),
        "wv": Spec((kvd, KH, D), (kv_axis, "kv_heads", "head_dim"), (kv_role, "out", "-"), init="fan_in"),
        "wo": Spec((H, D, E), ("heads", "head_dim", "embed"), ("in", "-", "out"), init="fan_in"),
        "gate": Spec((1,), ("mtp",), ("-",), init="zeros"),  # tanh-gated residual (llama-vision)
    }


def cross_kv_cache_specs(cfg: ModelConfig, batch: int, n_kv_tokens: int) -> Dict[str, Spec]:
    KH, D = cfg.n_kv_heads, cfg.resolved_head_dim
    ax = ("batch", "img_seq", "cache_kv_heads", "head_dim")
    dt = cfg.compute_dtype
    return {
        "ck": Spec((batch, n_kv_tokens, KH, D), ax, init="zeros", dtype=dt),
        "cv": Spec((batch, n_kv_tokens, KH, D), ax, init="zeros", dtype=dt),
    }


def cross_attn_precompute(p: Dict, kv_src: jax.Array, cfg: ModelConfig) -> Dict:
    cdt = cfg.compute_dtype
    k = jnp.einsum("bte,ehd->bthd", kv_src, p["wk"].astype(cdt))
    v = jnp.einsum("bte,ehd->bthd", kv_src, p["wv"].astype(cdt))
    return {"ck": k, "cv": v}


def cross_attn_apply(
    p: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    kv_src: Optional[jax.Array] = None,  # [B,T,kv_dim] (train path)
    kv_cache: Optional[Dict] = None,  # precomputed k/v (decode path)
    gated: bool = True,
) -> jax.Array:
    B, S, E = x.shape
    H, KH, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    cdt = cfg.compute_dtype
    q = jnp.einsum("bse,ehd->bshd", x, p["wq"].astype(cdt))
    if kv_cache is not None:
        k, v = kv_cache["ck"], kv_cache["cv"]
    else:
        kv = cross_attn_precompute(p, kv_src, cfg)
        k, v = kv["ck"], kv["cv"]
    qg = q.reshape(B, S, KH, H // KH, D)
    out = run_attention(qg, k, v, cfg, causal=False, scale=D ** -0.5,
                        decode=kv_cache is not None)
    out = out.reshape(B, S, H, D)
    y = jnp.einsum("bshd,hde->bse", out, p["wo"].astype(cdt))
    if gated:
        y = jnp.tanh(p["gate"].astype(cdt)) * y
    return shard_l(y, ("batch", "seq", "act_embed"))
