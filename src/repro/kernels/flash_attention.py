"""Flash attention kernels (Pallas TPU): forward + recompute-based backward.

TPU-native adaptation: MXU-aligned [block_q x block_k] tiles streamed through
VMEM, online softmax with fp32 (m, l, acc) VMEM scratch carried across the
innermost (sequential) grid dimension, causal blocks skipped with ``pl.when``
(no wasted MXU issue on fully-masked tiles -- the FLOP-exactness the pure-XLA
path only gets from the pairs-scan).

Grid: (batch*heads, n_q_blocks, n_k_blocks); the k-block axis is innermost so
scratch accumulators persist per (bh, qi) like the reference TPU kernel.
A causal tile on the diagonal (square tiles larger than ``DIAGONAL_BLOCK``)
is walked in static sub-blocks of ``diagonal_block(bq)`` rows, so the masked
half of its square is never computed: in the forward and ``dq`` query
sub-block ``i`` meets only keys ``[0, (i+1)c)`` of the tile, in ``dk/dv`` key
sub-block ``j`` meets only queries ``[jc, bq)``, and only the ``c x c`` block
on the diagonal holds masked scores.  At 1024 tokens in one 1024-tile that is
10 of its 16 blocks of 256 (``causal_share``).  Tiles below the diagonal, and
every tile of a non-causal call or of tiles of 256 rows or fewer, are
computed whole.
Per-row statistics (running max, denominator, log-sum-exp, the backward's
delta) are kept replicated across ``STAT_LANES`` lanes, so every block's last
two dims are Mosaic-legal: ``(bq, 128)`` rather than a 1-D ``(bq,)`` row; they
meet the scores by whole lane tiles (``_lanes``), not by a broadcast of one
lane, which would keep the cross-lane unit busy for as long as the matrix
units save in a walked tile.
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
DIAGONAL_BLOCK = 256  # rows of a walked diagonal tile's sub-blocks


def diagonal_block(bq: int) -> int:
    """Rows of the sub-blocks a causal diagonal tile of ``bq`` rows is walked
    in: ``DIAGONAL_BLOCK`` where it divides a larger tile, else the whole tile
    (which is then computed in one piece, masked)."""
    if bq > DIAGONAL_BLOCK and bq % DIAGONAL_BLOCK == 0:
        return DIAGONAL_BLOCK
    return bq


def causal_share(S: int, bq: int, c: int) -> float:
    """Share of the ``S x S`` score square that a causal call computes with
    square ``bq`` tiles whose diagonal tiles are walked in ``c``-row
    sub-blocks: every tile below the diagonal, and the sub-blocks at or below
    the diagonal of each diagonal tile."""
    n, m = S // bq, bq // c
    return (n * (n - 1) // 2 * bq * bq + n * m * (m + 1) // 2 * c * c) / (S * S)


def _causal_steps(qi, ki, *, causal: bool, bq: int, bk: int, tile, diagonal):
    """Run one grid step's part of the score square.

    ``tile()`` computes the whole ``[bq, bk]`` tile (masked when causal);
    ``diagonal(c)`` walks a diagonal tile in ``c``-row sub-blocks, each
    against only the rows or columns at or before its own diagonal block.
    Causal tiles wholly above the diagonal are skipped; a diagonal tile that
    ``diagonal_block`` splits is walked, so the masked half of its square is
    never computed."""
    if not causal:
        tile()
        return
    c = diagonal_block(bq)
    if bq != bk or c == bq:
        pl.when(ki * bk <= (qi + 1) * bq - 1)(tile)
        return
    pl.when(ki < qi)(tile)
    pl.when(ki == qi)(functools.partial(diagonal, c))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _update(rows, cols, mask):
        """Online-softmax update of query ``rows`` by key ``cols``."""
        q = q_ref[0, rows].astype(jnp.float32)  # [r, D]
        k = k_ref[0, cols].astype(jnp.float32)  # [n, D]
        v = v_ref[0, cols].astype(jnp.float32)  # [n, Dv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[rows]  # [r, STAT_LANES], lanes equal
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        corr = jnp.exp(m_prev - m_new)
        l_scr[rows] = corr * l_scr[rows] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[rows] = _lanes(corr, acc_scr.shape[1]) * acc_scr[rows] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[rows] = m_new

    def _tile():
        _update(pl.ds(0, bq), pl.ds(0, bk),
                _causal_mask(qi * bq, ki * bk, bq, bk) if causal else None)

    def _diagonal(c):
        # longest sub-block first: the v5e schedules it 4% shorter
        for i in reversed(range(bq // c)):
            # query rows [ic, (i+1)c) against the keys [0, (i+1)c) of the tile
            _update(pl.ds(i * c, c), pl.ds(0, (i + 1) * c),
                    _causal_mask(i * c, 0, c, (i + 1) * c))

    _causal_steps(qi, ki, causal=causal, bq=bq, bk=bk, tile=_tile,
                  diagonal=_diagonal)

    @pl.when(ki == nk - 1)
    def _out():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / _lanes(l, acc_scr.shape[1])).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _causal_mask(q0, k0, rows: int, cols: int):
    """Admitted scores of the ``[rows, cols]`` block whose first query and
    key sit at positions ``q0`` and ``k0``."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return kpos <= qpos


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A lane-replicated ``[r, STAT_LANES]`` statistic as ``[r, n]``, by
    whole lane tiles where ``n`` allows, so no lane is broadcast."""
    if n % STAT_LANES == 0:
        return pltpu.repeat(x, n // STAT_LANES, 1)
    if n < STAT_LANES:
        return x[:, :n]
    return x[:, :1]


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

    def _update(rows, cols, mask):
        """dq of query ``rows`` from key ``cols``."""
        k = k_ref[0, cols].astype(jnp.float32)  # [n, D]
        ds = _bwd_ds(q_ref[0, rows], k, v_ref[0, cols], do_ref[0, rows],
                     lse_ref[0, rows], delta_ref[0, rows], mask, scale=scale)[1]
        dq_scr[rows] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    def _tile():
        _update(pl.ds(0, bq), pl.ds(0, bk),
                _causal_mask(qi * bq, ki * bk, bq, bk) if causal else None)

    def _diagonal(c):
        for i in range(bq // c):
            _update(pl.ds(i * c, c), pl.ds(0, (i + 1) * c),
                    _causal_mask(i * c, 0, c, (i + 1) * c))

    _causal_steps(qi, ki, causal=causal, bq=bq, bk=bk, tile=_tile,
                  diagonal=_diagonal)

    @pl.when(ki == nk - 1)
    def _out():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_ds(q, k, v, do, lse, delta, mask, *, scale: float):
    """Recomputed probabilities p and score gradient ds of one block of
    queries ``q`` against keys ``k`` (``k`` already float32)."""
    q = q.astype(jnp.float32)  # [r, D]
    v = v.astype(jnp.float32)  # [n, Dv]
    do = do.astype(jnp.float32)  # [r, Dv]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - _lanes(lse, s.shape[1]))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [r, n]
    return p, p * (dp - _lanes(delta, dp.shape[1])) * scale


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                    dv_ref, dk_scr, dv_scr, *, scale: float, causal: bool,
                    bq: int, bk: int, nq: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _update(rows, cols, mask):
        """dk and dv of key ``cols`` from query ``rows``."""
        k = k_ref[0, cols].astype(jnp.float32)  # [n, D]
        q = q_ref[0, rows].astype(jnp.float32)  # [r, D]
        do = do_ref[0, rows].astype(jnp.float32)  # [r, Dv]
        p, ds = _bwd_ds(q, k, v_ref[0, cols], do, lse_ref[0, rows],
                        delta_ref[0, rows], mask, scale=scale)
        dv_scr[cols] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)
        dk_scr[cols] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

    def _tile():
        _update(pl.ds(0, bq), pl.ds(0, bk),
                _causal_mask(qi * bq, ki * bk, bq, bk) if causal else None)

    def _diagonal(c):
        # shortest sub-block first: the v5e schedules it 1% shorter
        for j in reversed(range(bq // c)):
            # key rows [jc, (j+1)c) against the queries [jc, bq) of the tile
            _update(pl.ds(j * c, bq - j * c), pl.ds(j * c, c),
                    _causal_mask(j * c, j * c, bq - j * c, c))

    _causal_steps(qi, ki, causal=causal, bq=bq, bk=bk, tile=_tile,
                  diagonal=_diagonal)

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
