"""Single-tile attention with rotary embedding fused in (Pallas TPU).

When the whole key sequence fits one tile (``S == T <= 128``: BERT-style
training and eval, a short prompt's cold prefill), a query row's softmax
needs no online statistics, and the rotary embedding, the scores, the
softmax and the weighted sum of values can all run in VMEM.  One forward
kernel and one backward kernel do that, so neither the ``[B, H, S, T]``
scores nor a float32 ``[B, S, H, D]`` gradient ever reaches HBM: the
kernels' operands are the bfloat16 q, k, v, o (and do, dq, dk, dv), a
``[B, H, S]`` float32 log-sum-exp and the rotary table.

Layout.  q, k and v arrive as the projection wrote them, ``[B, S, H*D]``
(heads side by side along the lanes), and o, dq, dk, dv leave in the same
layout, so no transpose surrounds the kernels.  A grid step takes ``rb``
batch rows and ``W`` lanes (``W / D`` heads, ``D`` dividing 128); inside it,
each group of 128 lanes (``128 / D`` heads) is rotated once, and k and v are
stacked ``128 / D`` times along the sublanes with the other heads' lanes
zeroed, so that one MXU call gives every head's scores and one gives every
head's output (the MXU's contraction is 128 deep, so a 64-wide head costs
the same either way).  The scores are held transposed, ``[T, S]`` per head,
so a query's statistics reduce over sublanes and come out as a lane-dense
``[1, S]`` row.

Rotary table.  ``cos`` and ``sin`` of the angles are computed by XLA from
the positions exactly as ``apply_rope`` computes them, ``[B, S, D/2]``, and
passed transposed, positions along the lanes: ``[B, D/2, S]`` cosines and
``[B, D, S]`` sines signed for ``rope(x) = x*cos + swap(x)*sin``, where
``swap`` exchanges each head's two halves (48 KB a row at D = 64).  A kernel
tiles a row's table along the sublanes to ``[128, S]`` and transposes it
once to the ``[S, 128]`` lane pattern of a group.

Schedule.  A grid step unrolls up to ``MAX_GROUPS`` groups and issues them
software-pipelined (``_pipelined``), so the compiler overlaps one group's
matmuls with another's vector work.

Numerics follow ``apply_rope`` + ``plain_attention`` (the ``xla`` backend):
the rotary in float32 rounded to bfloat16 before the MXU; QK^T on bfloat16
operands with float32 accumulation; softmax in float32 and p rounded to
bfloat16 for PV.  The backward recomputes p from the saved log-sum-exp,
keeps dp and ds in float32 and rounds ds to bfloat16 as the MXU operand of
dq and dk (what the default-precision dot on the float32 ds does on TPU);
the inverse rotary of dq and dk runs in float32 before the final rounding.
The causal mask is by index (key t <= query s): the keys are the queries'
own tokens.

Validated in interpret mode against the ``xla`` backend, values and
gradients (tests/test_attention_tile.py).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.layers.basic import rope_freqs

NEG_INF = -1e30
LANES = 128
MAX_S = 128  # one tile: the whole key sequence in one [S, S] score block
MAX_W = 1024  # lanes per grid step
MAX_GROUPS = 16  # 128-lane groups per grid step, over its batch rows


def fits(seq: int, heads: int, head_dim: int) -> bool:
    """Whether ``[B, seq, heads*head_dim]`` operands fit the kernels."""
    return (seq <= MAX_S and seq % 8 == 0 and head_dim % 2 == 0
            and LANES % head_dim == 0 and (heads * head_dim) % LANES == 0)


def _blocks(B: int, HD: int, D: int) -> Tuple[int, int]:
    """(rows, lanes) of one grid step.  The lanes take whole heads, and a
    number of them that keeps the log-sum-exp block Mosaic-legal (8 heads
    or all of them); the rows bring the step up to ``MAX_GROUPS`` groups
    for the compiler to interleave (with more, the registers spill and the
    backward outgrows its VMEM)."""
    w = HD
    for cand in range(min(HD, MAX_W), LANES - 1, -LANES):
        if HD % cand == 0 and (cand == HD or (cand // D) % 8 == 0):
            w = cand
            break
    rb = max(r for r in range(1, B + 1)
             if B % r == 0 and (r == 1 or r * (w // LANES) <= MAX_GROUPS))
    return rb, w


def rotary_table(positions: jax.Array, D: int, theta: float):
    """``apply_rope``'s cos and sin, transposed: ``[B, D/2, S]`` cosines
    and ``[B, D, S]`` sines signed for the first half of a head."""
    ang = positions[..., None].astype(jnp.float32) * rope_freqs(D, theta)
    sin = jnp.sin(ang)
    return (jnp.swapaxes(jnp.cos(ang), 1, 2),
            jnp.swapaxes(jnp.concatenate([-sin, sin], axis=-1), 1, 2))


# The kernel bodies below unroll every group of a grid step, thousands of
# operations, and are traced again for each train step that calls them; they
# are written with ``lax`` primitives, which trace directly, where the
# ``jax.numpy`` operators would each go through a jitted wrapper.

_NT = (((1,), (1,)), ((), ()))  # contract lanes: [M, 128] x [S, 128] -> [M, S]
_TN = (((0,), (0,)), ((), ()))  # contract rows:  [M, S] x [M, 128] -> [S, 128]
_LANE_GATHER = lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


def _f32(x):
    return lax.convert_element_type(x, jnp.float32)


def _rows(x, h: int, n: int):
    """Rows ``[h*n, (h+1)*n)`` of a 2-D value."""
    return lax.slice(x, (h * n, 0), ((h + 1) * n, x.shape[1]))


def _down(row, shape):
    """A per-query ``[S]`` statistic broadcast down the key rows."""
    return lax.broadcast_in_dim(row, shape, (1,))


def _rotary(cos_ref, sin_ref, r, D: int):
    """Row r's cos and signed sin in a group's lane pattern, ``[S, 128]``."""
    cos = lax.concatenate([cos_ref[r]] * (2 * LANES // D), 0)  # [128, S]
    sin = lax.concatenate([sin_ref[r]] * (LANES // D), 0)
    return lax.transpose(cos, (1, 0)), lax.transpose(sin, (1, 0))


def _swapper(S: int, D: int):
    """``swap(x)``: each head's two halves of an ``[S, 128]`` group
    exchanged along the lanes, one lane gather (a single cross-lane
    permute, where two rotations would need a select as well).  The lane
    pattern is built once per kernel."""
    lane = lax.broadcasted_iota(jnp.int32, (S, LANES), 1)
    partner = jnp.where(lane % D < D // 2, lane + D // 2, lane - D // 2)
    return lambda x: lax.gather(x, partner[..., None], _LANE_GATHER, (1, 1),
                                mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _heads(D: int, shape):
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return [lane // D == h for h in range(LANES // D)]


def _stack(x, heads, dt):
    """``[hg*S, 128]``: one copy of x per head of the group, the other heads'
    lanes zeroed, rounded to the MXU operand type ``dt``."""
    zero = lax.full(x.shape, 0, x.dtype)
    return lax.convert_element_type(
        lax.concatenate([lax.select(sel, x, zero) for sel in heads], 0), dt)


def _causal(S: int):
    t = lax.broadcasted_iota(jnp.int32, (S, S), 0)
    s = lax.broadcasted_iota(jnp.int32, (S, S), 1)
    return t <= s


def _rope(x, cos, sin, swap):
    return lax.add(lax.mul(x, cos), lax.mul(swap(x), sin))


def _rope_t(y, cos, sin, swap):
    """The rotary's transpose: ``y*cos - swap(y)*sin``."""
    return lax.sub(lax.mul(y, cos), lax.mul(swap(y), sin))


def _rotated(q_ref, k_ref, r, cols, cos, sin, swap):
    """This group's q (rounded to the operand type) and k (float32) after
    the rotary."""
    q = _rope(_f32(q_ref[r, :, cols]), cos, sin, swap)
    k = _rope(_f32(k_ref[r, :, cols]), cos, sin, swap)
    return lax.convert_element_type(q, q_ref.dtype), k


def _pipelined(n: int, stages, skew: int):
    """Run the stages in turn on items 0..n-1, stage k of item i at program
    step i + k*skew.  The kernel body is one basic block and the compiler
    schedules it close to program order: issuing a later group's matmuls
    before an earlier group's vector work lets the MXU, the vector units
    and the cross-lane unit overlap across groups (on v5e it shortens the
    static schedule of a BERT-Large level-0 grid step by 19% forward and
    30% backward)."""
    state = [None] * n
    for t in range(n + skew * (len(stages) - 1)):
        for k, stage in enumerate(stages):
            i = t - k * skew
            if 0 <= i < n:
                state[i] = stage(i if k == 0 else state[i])


def _fwd_kernel(cos_ref, sin_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale: float, causal: bool, D: int):
    rb, S, W = q_ref.shape
    dt = q_ref.dtype  # the MXU operands' type: rounded to it as the layer does
    g = LANES
    hg = g // D
    heads = _heads(D, (S, g))
    tables = [_rotary(cos_ref, sin_ref, r, D) for r in range(rb)]
    swap = _swapper(S, D)
    mask = _causal(S) if causal else None
    neg = lax.full((S, S), NEG_INF, jnp.float32)

    def scores(i):
        r, c = divmod(i, W // g)
        cols = pl.ds(c * g, g)
        q, kf = _rotated(q_ref, k_ref, r, cols, *tables[r], swap)
        ks = _stack(kf, heads, dt)
        vs = _stack(_f32(v_ref[r, :, cols]), heads, dt)
        # every head's transposed scores at once: [hg*T, S]
        s = lax.mul(lax.dot_general(ks, q, _NT,
                                    preferred_element_type=jnp.float32), scale)
        return r, c, s, vs

    def softmax(st):
        r, c, s, vs = st
        ps, lse = [], []
        for h in range(hg):
            sh = _rows(s, h, S)
            if causal:
                sh = lax.select(mask, sh, neg)
            m = lax.reduce_max(sh, (0,))  # [S]
            e = lax.exp(lax.sub(sh, _down(m, sh.shape)))
            l = lax.reduce_sum(e, (0,))
            ps.append(lax.convert_element_type(lax.div(e, _down(l, e.shape)), dt))
            lse.append(lax.broadcast_in_dim(lax.add(m, lax.log(l)), (1, S), (1,)))
        return r, c, lax.concatenate(ps, 0), vs, lax.concatenate(lse, 0)

    def output(st):
        r, c, p, vs, lse = st
        # the zeroed lanes of vs keep each head's p on its own lanes
        o = lax.dot_general(p, vs, _TN, preferred_element_type=jnp.float32)
        o_ref[r, :, pl.ds(c * g, g)] = lax.convert_element_type(o, o_ref.dtype)
        lse_ref[r, pl.ds(c * hg, hg), :] = lse

    _pipelined(rb * (W // g), [scores, softmax, output], skew=3)


def _bwd_kernel(cos_ref, sin_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, *, scale: float, causal: bool, D: int):
    rb, S, W = q_ref.shape
    dt = q_ref.dtype
    g = LANES
    hg = g // D
    heads = _heads(D, (S, g))
    tables = [_rotary(cos_ref, sin_ref, r, D) for r in range(rb)]
    swap = _swapper(S, D)
    mask = _causal(S) if causal else None
    neg = lax.full((S, S), NEG_INF, jnp.float32)
    zero = lax.full((S, g), 0, jnp.float32)

    def scores(i):
        r, c = divmod(i, W // g)
        cols = pl.ds(c * g, g)
        q, kf = _rotated(q_ref, k_ref, r, cols, *tables[r], swap)
        ks = _stack(kf, heads, dt)
        vs = _stack(_f32(v_ref[r, :, cols]), heads, dt)
        do = do_ref[r, :, cols]
        s = lax.mul(lax.dot_general(ks, q, _NT,
                                    preferred_element_type=jnp.float32), scale)
        dp = lax.dot_general(vs, do, _NT, preferred_element_type=jnp.float32)
        return r, c, q, ks, do, s, dp

    def grads(st):
        r, c, q, ks, do, s, dp = st
        cos, sin = tables[r]
        cols = pl.ds(c * g, g)
        lse = lse_ref[r, pl.ds(c * hg, hg), :]
        ps, dss = [], []
        for h in range(hg):
            sh = _rows(s, h, S)
            if causal:
                sh = lax.select(mask, sh, neg)
            p = lax.exp(lax.sub(sh, lax.broadcast_in_dim(
                _rows(lse, h, 1), sh.shape, (0, 1))))  # [T, S]
            dph = _rows(dp, h, S)
            delta = lax.reduce_sum(lax.mul(p, dph), (0,))  # [S]
            ds = lax.mul(lax.mul(p, lax.sub(dph, _down(delta, dph.shape))), scale)
            ps.append(lax.convert_element_type(p, dt))
            dss.append(lax.convert_element_type(ds, dt))
        p, ds = lax.concatenate(ps, 0), lax.concatenate(dss, 0)
        dq = lax.dot_general(ds, ks, _TN, preferred_element_type=jnp.float32)
        dks = lax.dot_general(ds, q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        dvs = lax.dot_general(p, do, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        dk, dv = zero, zero
        for h, sel in enumerate(heads):
            dk = lax.add(dk, lax.select(sel, _rows(dks, h, S), zero))
            dv = lax.add(dv, lax.select(sel, _rows(dvs, h, S), zero))
        dq_ref[r, :, cols] = lax.convert_element_type(
            _rope_t(dq, cos, sin, swap), dq_ref.dtype)
        dk_ref[r, :, cols] = lax.convert_element_type(
            _rope_t(dk, cos, sin, swap), dk_ref.dtype)
        dv_ref[r, :, cols] = lax.convert_element_type(dv, dv_ref.dtype)

    _pipelined(rb * (W // g), [scores, grads], skew=3)


def _specs(B: int, S: int, HD: int, D: int):
    rb, w = _blocks(B, HD, D)
    grid = (B // rb, HD // w)
    rows = pl.BlockSpec((rb, S, w), lambda b, j: (b, 0, j))
    cos = pl.BlockSpec((rb, D // 2, S), lambda b, j: (b, 0, 0))
    sin = pl.BlockSpec((rb, D, S), lambda b, j: (b, 0, 0))
    lse = pl.BlockSpec((rb, w // D, S), lambda b, j: (b, j, 0))
    return grid, rows, [cos, sin], lse


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def _fwd_call(q, k, v, cos, sin, *, D: int, causal: bool, scale: float,
              interpret: bool):
    B, S, HD = q.shape
    grid, rows, table, lse_spec = _specs(B, S, HD, D)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, D=D),
        grid=grid,
        in_specs=table + [rows, rows, rows],
        out_specs=[rows, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((B, S, HD), q.dtype),
                   jax.ShapeDtypeStruct((B, HD // D, S), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="attention_tile_fwd",
    )(cos, sin, q, k, v)


def _bwd_call(q, k, v, cos, sin, lse, do, *, D: int, causal: bool,
              scale: float, interpret: bool):
    B, S, HD = q.shape
    grid, rows, table, lse_spec = _specs(B, S, HD, D)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal, D=D),
        grid=grid,
        in_specs=table + [rows, rows, rows, rows, lse_spec],
        out_specs=[rows, rows, rows],
        out_shape=[jax.ShapeDtypeStruct((B, S, HD), q.dtype),
                   jax.ShapeDtypeStruct((B, S, HD), k.dtype),
                   jax.ShapeDtypeStruct((B, S, HD), v.dtype)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="attention_tile_bwd",
    )(cos, sin, q, k, v, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _tile(q, k, v, cos, sin, D, causal, scale, interpret):
    return _fwd_call(q, k, v, cos, sin, D=D, causal=causal, scale=scale,
                     interpret=interpret)[0]


def _tile_fwd(q, k, v, cos, sin, D, causal, scale, interpret):
    o, lse = _fwd_call(q, k, v, cos, sin, D=D, causal=causal, scale=scale,
                       interpret=interpret)
    return o, (q, k, v, cos, sin, lse)


def _tile_bwd(D, causal, scale, interpret, res, do):
    q, k, v, cos, sin, lse = res
    dq, dk, dv = _bwd_call(q, k, v, cos, sin, lse, do, D=D, causal=causal,
                           scale=scale, interpret=interpret)
    return dq, dk, dv, None, None


_tile.defvjp(_tile_fwd, _tile_bwd)


def attention_tile(
    q: jax.Array,  # [B, S, H*D] before the rotary embedding
    k: jax.Array,  # [B, S, H*D] before the rotary embedding (H heads)
    v: jax.Array,  # [B, S, H*D]
    positions: jax.Array,  # [B, S] rotary positions
    *,
    head_dim: int,
    causal: bool,
    scale: float,
    theta: float,
    interpret: bool = False,
) -> jax.Array:
    """Differentiable rotary + attention over one tile -> o ``[B, S, H*D]``.

    K/V carry as many heads as q: GQA callers repeat them over the query
    groups first, and the group-sum of dk/dv falls out of the repeat's VJP.
    """
    B, S, HD = q.shape
    if not fits(S, HD // head_dim, head_dim) or HD % head_dim:
        raise ValueError(f"S={S}, H*D={HD}, D={head_dim} do not fit one tile")
    cos, sin = rotary_table(positions, int(head_dim), float(theta))
    return _tile(q, k, v, cos, sin, int(head_dim), bool(causal), float(scale),
                 bool(interpret))
