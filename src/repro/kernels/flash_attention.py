"""Flash attention kernels (Pallas TPU): forward + recompute-based backward.

TPU-native adaptation: MXU-aligned [block_q x block_k] tiles streamed through
VMEM, online softmax with fp32 (m, l, acc) VMEM scratch carried across the
innermost (sequential) grid dimension, causal blocks skipped with ``pl.when``
(no wasted MXU issue on fully-masked tiles -- the FLOP-exactness the pure-XLA
path only gets from the pairs-scan).

Grid: (batch*heads, n_q_blocks, n_k_blocks); the k-block axis is innermost so
scratch accumulators persist per (bh, qi) like the reference TPU kernel.
Per-row statistics (running max, denominator, log-sum-exp, the backward's
delta) are kept replicated across ``STAT_LANES`` lanes, so every block's last
two dims are Mosaic-legal: ``(bq, 128)`` rather than a 1-D ``(bq,)`` row.
Masked scores are the finite ``NEG_INF``: the first k-block of every row holds
key 0, which causality always admits, so the running max is finite from the
first computed tile and masked probabilities come out exactly 0.

The backward follows the flash-attention recipe (same as the XLA-level
``_flash_xla_bwd`` in layers/attention.py): save only (q, k, v, out, lse),
recompute the probabilities per tile from the saved log-sum-exp, and run two
kernels -- one accumulating dq over k-blocks, one accumulating (dk, dv) over
q-blocks -- so no O(S^2) intermediate ever touches HBM.
``flash_attention_with_vjp`` packages fwd+bwd behind ``jax.custom_vjp``.

The three calls are named ``flash_attention_fwd``, ``flash_attention_dq``
and ``flash_attention_dkv``: the names the kernels carry in a profiler trace.

Validated in interpret mode against ref.naive_attention, values and grads
(tests/test_kernels.py, tests/test_dispatch.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import round_up, sublane, tile

NEG_INF = -1e30
STAT_LANES = 128  # per-row statistics, replicated across one lane tile


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0].astype(jnp.float32)  # [bk, Dv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk), s, NEG_INF)
        m_prev = m_scr[...]  # [bq, STAT_LANES], lanes equal
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = corr[:, :1] * acc_scr[...] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal:
        # skip tiles strictly above the diagonal band
        pl.when(ki * bk <= (qi + 1) * bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _out():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _causal_mask(qi, ki, bq: int, bk: int):
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return kpos <= qpos


def _stat_lanes(x: jax.Array) -> jax.Array:
    """[BH, S] row statistic -> [BH, S, STAT_LANES] kernel operand."""
    return jnp.broadcast_to(x[..., None], x.shape + (STAT_LANES,))


def _fwd_call(q, k, v, *, causal: bool, scale: float, bq: int, bk: int,
              interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """Flattened [B*H, S, D] forward; returns (out, lse [B*H, S])."""
    BH, S, D = q.shape
    T = k.shape[1]
    Dv = v.shape[2]
    nq, nk = S // bq, T // bk
    kern = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, nk=nk)
    out, lse = pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, STAT_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, S, STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, STAT_LANES), jnp.float32),
            pltpu.VMEM((bq, STAT_LANES), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out, lse[..., 0]


def flash_tiles(S: int, T: int, block_q: int, block_k: int, causal: bool,
                dtype) -> Optional[Tuple[int, int, int, int]]:
    """``(bq, bk, S_padded, T_padded)`` for Mosaic-legal q/k blocks, or None
    when the kernel cannot express the shape: padded keys need a mask, which
    only causal attention with ``S == T`` supplies (a padded key sits past
    every real query)."""
    bq, Sp = tile(S, block_q, sublane(dtype))
    bk, Tp = tile(T, block_k, sublane(dtype))
    if Tp != T and not (causal and S == T):
        return None
    if causal and S == T and Sp != Tp:
        # one padded length for both, so the diagonal stays aligned
        Sp = Tp = round_up(S, bq * bk // math.gcd(bq, bk))
    return bq, bk, Sp, Tp


def _resolve_blocks(S: int, T: int, block_q: int, block_k: int, causal: bool,
                    dtype) -> Tuple[int, int, int, int]:
    tiles = flash_tiles(S, T, block_q, block_k, causal, dtype)
    if tiles is None:
        raise ValueError(f"S={S} T={T} (causal={causal}) needs padded keys "
                         f"the kernel cannot mask; blocks ({block_q},{block_k})")
    return tiles


def _pad_seq(x: jax.Array, n: int) -> jax.Array:
    """Zero-pad the sequence dim (2) of [B, H, S, D] to ``n``."""
    if x.shape[2] == n:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))


def flash_attention(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,  # [B, H, T, D]
    v: jax.Array,  # [B, H, T, Dv]
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Forward-only flash attention (no custom gradient)."""
    B, H, S, D = q.shape
    T = k.shape[2]
    Dv = v.shape[3]
    scale = D ** -0.5 if scale is None else scale
    bq, bk, Sp, Tp = _resolve_blocks(S, T, block_q, block_k, causal, q.dtype)
    out, _ = _fwd_call(_pad_seq(q, Sp).reshape(B * H, Sp, D),
                       _pad_seq(k, Tp).reshape(B * H, Tp, D),
                       _pad_seq(v, Tp).reshape(B * H, Tp, Dv), causal=causal,
                       scale=scale, bq=bq, bk=bk, interpret=interpret)
    return out.reshape(B, H, Sp, Dv)[:, :, :S]


# ---------------------------------------------------------------------------
# backward kernels (recompute p from saved lse; flash-attention recipe)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale: float, causal: bool, bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute():
        k = k_ref[0].astype(jnp.float32)  # [bk, D]
        ds = _bwd_ds(q_ref, k, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
                     scale=scale, causal=causal, bq=bq, bk=bk)[1]
        dq_scr[...] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki * bk <= (qi + 1) * bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _out():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_ds(q_ref, k, v_ref, do_ref, lse_ref, delta_ref, qi, ki, *,
            scale: float, causal: bool, bq: int, bk: int):
    """Recomputed probabilities p and score gradient ds of one [bq, bk] tile."""
    q = q_ref[0].astype(jnp.float32)  # [bq, D]
    v = v_ref[0].astype(jnp.float32)  # [bk, Dv]
    do = do_ref[0].astype(jnp.float32)  # [bq, Dv]
    lse = lse_ref[0][:, :1]  # [bq, 1]
    delta = delta_ref[0][:, :1]  # [bq, 1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        s = jnp.where(_causal_mask(qi, ki, bq, bk), s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [bq, bk]
    return p, p * (dp - delta) * scale


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                    dv_ref, dk_scr, dv_scr, *, scale: float, causal: bool,
                    bq: int, bk: int, nq: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        k = k_ref[0].astype(jnp.float32)  # [bk, D]
        p, ds = _bwd_ds(q_ref, k, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
                        scale=scale, causal=causal, bq=bq, bk=bk)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    if causal:
        pl.when((qi + 1) * bq - 1 >= ki * bk)(_compute)
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _out():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, out, lse, do, *, causal: bool, scale: float, bq: int,
              bk: int, interpret: bool):
    """Flattened [B*H, S, D] backward; returns (dq, dk, dv)."""
    BH, S, D = q.shape
    T = k.shape[1]
    Dv = v.shape[2]
    nq, nk = S // bq, T // bk
    # rowwise correction term D_i = sum_v do*out (cheap elementwise pass)
    delta = _stat_lanes(jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                                axis=-1))
    lse = _stat_lanes(lse)

    q_spec_i = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    k_spec_j = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))
    v_spec_j = pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, j, 0))
    do_spec_i = pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0))
    row_spec_i = pl.BlockSpec((1, bq, STAT_LANES), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[q_spec_i, k_spec_j, v_spec_j, do_spec_i, row_spec_i, row_spec_i],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, delta)

    # (dk, dv) grid transposes the block roles: k-block outer, q-block inner
    q_spec_j = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, 0))
    k_spec_i = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0))
    v_spec_i = pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, i, 0))
    do_spec_j = pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, j, 0))
    row_spec_j = pl.BlockSpec((1, bq, STAT_LANES), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq),
        grid=(BH, nk, nq),
        in_specs=[q_spec_j, k_spec_i, v_spec_i, do_spec_j, row_spec_j, row_spec_j],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, Dv), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, Dv), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-VJP packaging


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_vjp(q, k, v, causal: bool, scale: float, bq: int, bk: int,
               interpret: bool):
    B, H, S, D = q.shape
    T, Dv = k.shape[2], v.shape[3]
    out, _ = _fwd_call(q.reshape(B * H, S, D), k.reshape(B * H, T, D),
                       v.reshape(B * H, T, Dv), causal=causal, scale=scale,
                       bq=bq, bk=bk, interpret=interpret)
    return out.reshape(B, H, S, Dv)


def _flash_vjp_fwd(q, k, v, causal, scale, bq, bk, interpret):
    B, H, S, D = q.shape
    T, Dv = k.shape[2], v.shape[3]
    out, lse = _fwd_call(q.reshape(B * H, S, D), k.reshape(B * H, T, D),
                         v.reshape(B * H, T, Dv), causal=causal, scale=scale,
                         bq=bq, bk=bk, interpret=interpret)
    return out.reshape(B, H, S, Dv), (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, bq, bk, interpret, res, do):
    q, k, v, out, lse = res
    B, H, S, D = q.shape
    T, Dv = k.shape[2], v.shape[3]
    dq, dk, dv = _bwd_call(
        q.reshape(B * H, S, D), k.reshape(B * H, T, D), v.reshape(B * H, T, Dv),
        out, lse, do.reshape(B * H, S, Dv), causal=causal, scale=scale,
        bq=bq, bk=bk, interpret=interpret)
    return (dq.reshape(B, H, S, D), dk.reshape(B, H, T, D),
            dv.reshape(B, H, T, Dv))


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_with_vjp(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,  # [B, H, T, D]
    v: jax.Array,  # [B, H, T, Dv]
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Differentiable flash attention: Pallas forward AND backward kernels.

    Heads must match between q and k/v -- GQA callers broadcast KV over the
    query groups first so the group-sum of dk/dv falls out of the broadcast's
    own VJP (see layers/attention.py).
    """
    B, H, S, D = q.shape
    T = k.shape[2]
    scale = D ** -0.5 if scale is None else scale
    bq, bk, Sp, Tp = _resolve_blocks(S, T, block_q, block_k, causal, q.dtype)
    out = _flash_vjp(_pad_seq(q, Sp), _pad_seq(k, Tp), _pad_seq(v, Tp),
                     bool(causal), float(scale), bq, bk, bool(interpret))
    return out[:, :, :S]
