"""The paper's three operators on arbitrary models (Coalescing, De-coalescing,
Interpolation), driven by per-family :class:`~repro.core.plans.ProjectionPlan`
objects over the per-leaf logical-axis metadata.

For every width-coalescible logical axis (embed, mlp, heads, kv_heads, lora
ranks, expert dims, ...) one shared set of projection matrices is built --
which *is* the Appendix-A constraint structure: residual stream, Q/K alignment
and norm scales automatically share their F.  The "layers" axis is handled by
the depth matrices R/G per stage.  Protected axes (head_dim, rope dims,
d_state, conv taps, vocab, per-head recurrent memories) are never projected;
see DESIGN.md §4.

Which axes coalesce, which are protected, and which per-leaf roles get
rewritten (e.g. the MoE "experts" axis under expert merging) is decided by
``repro.core.plans.build_plan`` -- ``coalesce_config`` / ``build_level_maps``
here are thin compatibility wrappers over it, and every ``make_*_fn`` accepts
an explicit ``plan=`` so callers that already built one (the V-cycle runner)
don't re-derive it.

Execution: for the paper's main "stack" width variant the F/T contractions are
pair merges and duplications, so the leaves route through the matrix-free
fused kernels behind ``repro.kernels.dispatch`` (``coalesce_pair`` /
``interp_axpy``; one HBM pass, no F matrix, no MXU) -- the "adj" variant,
``embed_cat2`` block-diagonal matrices and depth R/G keep the dense-matrix
``tensordot`` path.  All of it stays jit-compatible: backend resolution is
trace-time, so ``vcycle`` level transitions remain host-round-trip-free.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, MultiLevelConfig
from repro.core import projections as proj
from repro.core.plans import (LevelMaps, ProjectionPlan, WIDTH_AXES,
                              axis_sizes, build_plan, normalize_overrides)
from repro.kernels import dispatch as kdispatch
from repro.param import Spec, is_spec


def coalesce_config(cfg: ModelConfig, ml: Optional[MultiLevelConfig] = None,
                    *, width: bool = True, depth: bool = True) -> ModelConfig:
    """The next-level (smaller) model config: width and depth halved.

    Compatibility wrapper over ``plans.build_plan(...).small_cfg`` -- the
    halving rules live in the per-family hooks now, so config derivation and
    map construction cannot drift apart.  ``width``/``depth`` switches support
    the single-direction baselines (StackBERT = depth-only, bert2BERT =
    width-only).
    """
    return build_plan(cfg, ml, width=width, depth=depth).small_cfg


def build_level_maps(cfg: ModelConfig, ml: MultiLevelConfig,
                     *, width: bool = True, depth: bool = True) -> LevelMaps:
    """Compatibility wrapper over ``plans.build_plan(...).build_maps()``."""
    return build_plan(cfg, ml, width=width, depth=depth).build_maps()


# ---------------------------------------------------------------------------
# applying the projections to a parameter tree


def _contract(w: jax.Array, dim: int, mat: jax.Array, mat_axis: int) -> jax.Array:
    """Contract w's ``dim`` with mat's ``mat_axis``; result axis moved back."""
    out = jnp.tensordot(w, mat, axes=([dim], [mat_axis]))
    return jnp.moveaxis(out, -1, dim)


def _stack_coalesce(w: jax.Array, dim: int, w0: float, backend) -> jax.Array:
    """Matrix-free "stack"-variant coalescing of ``dim``: fold the leaf to 2D
    and merge pairs (i, i + n/2) in one fused pass (no F matrix, no matmul)."""
    n = w.shape[dim]
    rest = tuple(s for i, s in enumerate(w.shape) if i != dim)
    w2 = jnp.moveaxis(w, dim, 0).reshape(n, -1)
    out = kdispatch.dispatch("coalesce_pair", w2, axis=0, w0=w0, backend=backend)
    return jnp.moveaxis(out.reshape((n // 2,) + rest), 0, dim)


def _stack_decoalesce(w: jax.Array, dim: int, w0: float) -> jax.Array:
    """Matrix-free "stack"-variant de-coalescing: T duplication is a pure
    gather -- tile the halved axis twice, scaled by the paper's normalization
    weight (T_out rows are 1.0, T_in rows 0.5).

    Duplication is broadcast+reshape, NOT ``concatenate([w, w])``: XLA's SPMD
    partitioner has miscompiled a concat whose operands alias the same
    *sharded* tensor (the halves got summed), and the aliasing survives a
    ``w + 0.0`` copy via CSE.  Broadcast lowers cleanly under any sharding
    and is the same single HBM pass."""
    lead = jnp.moveaxis(w, dim, 0)
    dup = jnp.broadcast_to(lead[None], (2,) + lead.shape)
    dup = dup.reshape((2 * lead.shape[0],) + lead.shape[1:])
    dup = jnp.moveaxis(dup, 0, dim)
    if w0 == 1.0:
        return dup
    return (w0 * dup.astype(jnp.float32)).astype(w.dtype)


def _width_leaf(w, spec: Spec, width: Dict[str, proj.WidthMats], direction: str,
                role_overrides, backend=None, fused: bool = True):
    overrides = normalize_overrides(role_overrides)
    for d, (ax, role) in enumerate(zip(spec.axes, spec.roles)):
        if ax in overrides and ax in width:
            # plan-level role rewrite, e.g. expert pair-averaging: the leaf
            # declares "experts" protected, the MoE plan flips it to "out"
            role = overrides[ax]
        if ax not in width or role not in ("in", "out"):
            continue
        m = width[ax]
        if fused and getattr(m, "variant", None) == "stack":
            # the "stack" averaging matrices ARE pair merges/duplications:
            # route through the fused kernels instead of materializing F
            # (F_out weights 0.5, F_in 1.0; T_out 1.0, T_in 0.5 -- the
            # paper's normalization, pinned by kernels/ref.py oracles)
            if direction == "coalesce":
                w = _stack_coalesce(w, d, 0.5 if role == "out" else 1.0, backend)
            else:
                w = _stack_decoalesce(w, d, 1.0 if role == "out" else 0.5)
        elif direction == "coalesce":
            w = _contract(w, d, m.F_out, 0) if role == "out" else _contract(w, d, m.F_in, 1)
        else:
            w = _contract(w, d, m.T_out, 0) if role == "out" else _contract(w, d, m.T_in, 1)
    return w


def _depth_leaf(w, spec: Spec, dm: proj.DepthMats, direction: str):
    if not spec.axes or spec.axes[0] != "layers":
        return w
    if direction == "coalesce":
        return jnp.einsum("l...,lj->j...", w, dm.R)  # R: [L, L2]
    return jnp.einsum("l...,lj->j...", w, dm.G)  # G: [L2, L]


def _project_tree(params, specs, maps: LevelMaps, direction: str,
                  role_overrides=None, depth_key: Optional[str] = None,
                  backend: Optional[str] = None, fused: bool = True):
    """Recurse through the tree, tracking which stage we are under so the right
    depth matrices apply.  ``role_overrides`` is the plan's per-axis role
    rewrite dict (a bare bool is accepted for pre-plan call sites, meaning
    ``cfg.coalesce_experts``)."""
    role_overrides = normalize_overrides(role_overrides)

    def rec(p, s, dkey):
        if is_spec(s):
            w = _width_leaf(p, s, maps.width, direction, role_overrides,
                            backend=backend, fused=fused)
            if dkey is not None and dkey in maps.depth:
                w = _depth_leaf(w, s, maps.depth[dkey], direction)
            return w
        out = {}
        for k in s:
            sub_dkey = dkey
            if k.startswith("stage_"):
                sub_dkey = k
            elif k == "encoder":
                sub_dkey = "encoder"
            out[k] = rec(p[k], s[k], sub_dkey)
        return out

    return rec(params, specs, depth_key)


def coalesce(params, specs, cfg: ModelConfig, ml: MultiLevelConfig,
             maps: Optional[LevelMaps] = None, *, fused: bool = True,
             plan: Optional[ProjectionPlan] = None):
    """Paper Algorithm 2: width then depth (they commute on disjoint axes)."""
    plan = plan or build_plan(cfg, ml)
    maps = (maps or plan.build_maps()).as_jnp()
    return _project_tree(params, specs, maps, "coalesce", plan.role_overrides,
                         backend=cfg.kernel_backend or None, fused=fused)


def decoalesce(params_small, specs, cfg: ModelConfig, ml: MultiLevelConfig,
               maps: Optional[LevelMaps] = None, *, fused: bool = True,
               plan: Optional[ProjectionPlan] = None):
    """Paper Algorithm 3: depth then width.  ``specs``/``cfg`` are the LARGE
    level's; ``params_small`` the small level's parameters."""
    plan = plan or build_plan(cfg, ml)
    maps = (maps or plan.build_maps()).as_jnp()
    return _project_tree(params_small, specs, maps, "decoalesce",
                         plan.role_overrides,
                         backend=cfg.kernel_backend or None, fused=fused)


def interpolate(params_large, params_decoalesced, alpha: float,
                backend: Optional[str] = None):
    """Paper Algorithm 4 / Eq. 13: M <- (1-a) M + a D(M_small).

    Each leaf runs through the fused ``interp_axpy`` kernel (one read of a and
    b, one write -- the memory-bound pass the Pallas kernel targets at scale)."""
    return jax.tree.map(
        lambda a, b: kdispatch.dispatch("interp_axpy", a, b, alpha,
                                        backend=backend),
        params_large, params_decoalesced)


def make_coalesce_fn(specs, cfg: ModelConfig, ml: MultiLevelConfig,
                     *, width: bool = True, depth: bool = True,
                     fused: bool = True, out_shardings=None,
                     plan: Optional[ProjectionPlan] = None):
    """jit'd level-transition.  "stack"-variant width axes route through the
    matrix-free fused kernels (repro.kernels.dispatch); everything else runs
    as sharded einsums.  ``fused=False`` forces the dense-matrix path (the
    equivalence oracle for tests/benchmarks).  ``out_shardings`` (a
    NamedSharding tree for the TARGET level's params) makes the projection
    sharded-in, sharded-out under a mesh -- no host round trip, no gather.
    Pass ``plan`` when one is already built (the V-cycle runner does); it must
    match ``(cfg, ml, width, depth)``."""
    plan = plan or build_plan(cfg, ml, width=width, depth=depth)
    maps = plan.build_maps().as_jnp()
    backend = cfg.kernel_backend or None
    return jax.jit(lambda p: _project_tree(p, specs, maps, "coalesce",
                                           plan.role_overrides,
                                           backend=backend, fused=fused),
                   out_shardings=out_shardings)


def make_decoalesce_fn(specs, cfg: ModelConfig, ml: MultiLevelConfig,
                       *, width: bool = True, depth: bool = True,
                       fused: bool = True, out_shardings=None,
                       plan: Optional[ProjectionPlan] = None):
    plan = plan or build_plan(cfg, ml, width=width, depth=depth)
    maps = plan.build_maps().as_jnp()
    backend = cfg.kernel_backend or None
    return jax.jit(lambda p: _project_tree(p, specs, maps, "decoalesce",
                                           plan.role_overrides,
                                           backend=backend, fused=fused),
                   out_shardings=out_shardings)


def make_interpolate_fn(alpha: float, backend: Optional[str] = None,
                        out_shardings=None):
    return jax.jit(lambda a, b: interpolate(a, b, alpha, backend=backend),
                   out_shardings=out_shardings)


def make_draft_projection(specs, cfg: ModelConfig,
                          ml: Optional[MultiLevelConfig] = None,
                          *, width: bool = True, depth: bool = True,
                          out_shardings=None) -> Tuple[ModelConfig, Any]:
    """Serving-time self-speculative draft: ``(draft_cfg, project_fn)``.

    The level-1 coalesced model is a deterministic *projection* of the
    serving params -- a free, always-in-sync draft model for speculative
    decoding: no separate training run, no second checkpoint to distribute.
    ``project_fn(params) -> draft_params`` is the jit'd Coalescing transition
    (sharded-in/sharded-out when ``out_shardings`` is given); re-invoke it
    whenever the serving params change (hot weight reload) and the draft
    stays in sync by construction.

    ``width``/``depth`` pick the projection direction: width-only drafts
    track the full model most closely (width de-coalescing is exactly
    function-preserving for untied embeddings, see tests/test_operators.py),
    full level-1 (both) is the cheapest draft the paper defines.
    """
    ml = ml or MultiLevelConfig()
    plan = build_plan(cfg, ml, width=width, depth=depth)
    project = make_coalesce_fn(specs, cfg, ml, width=width, depth=depth,
                               out_shardings=out_shardings, plan=plan)
    return plan.small_cfg, project
