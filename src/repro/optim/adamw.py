"""AdamW with warmup-cosine/linear schedules, global-norm clipping, decoupled
weight decay masked to >=2D weight matrices (norm scales / biases undecayed).

Optimizer state mirrors the parameter Spec tree (same logical axes), so it
shards identically (FSDP over data-like axes) and the dry-run can build
ShapeDtypeStructs for the full (params, m, v) triple without allocation.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import TrainConfig
from repro.param import Spec, is_spec


def lr_at(step: jax.Array, tc: TrainConfig) -> jax.Array:
    """Warmup then cosine/linear/constant decay (matches the paper's setup)."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(tc.warmup_steps, 1), 1.0)
    total = max(tc.steps - tc.warmup_steps, 1)
    frac = jnp.clip((step - tc.warmup_steps) / total, 0.0, 1.0)
    if tc.schedule == "cosine":
        decay = tc.end_lr_frac + (1 - tc.end_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    elif tc.schedule == "linear":
        decay = 1.0 - (1.0 - tc.end_lr_frac) * frac
    else:
        decay = jnp.ones_like(frac)
    return tc.peak_lr * warm * decay


def adamw_init_specs(param_specs, tc: TrainConfig):
    """Spec tree for (m, v) mirroring the parameter specs (same logical axes)."""

    def one(s: Spec) -> Spec:
        return Spec(s.shape, s.axes, s.roles, init="zeros", dtype=tc.opt_dtype)

    return {
        "m": jax.tree.map(one, param_specs, is_leaf=is_spec),
        "v": jax.tree.map(one, param_specs, is_leaf=is_spec),
        "count": Spec((), (), (), init="zeros", dtype=jnp.int32),
    }


def adamw_init(params, tc: TrainConfig):
    zeros = lambda p: jnp.zeros(p.shape, tc.opt_dtype)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }


def _clip_by_global_norm(grads, max_norm: float):
    g2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    gn = jnp.sqrt(g2)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), gn


def adamw_update(
    params, grads, opt_state, tc: TrainConfig
) -> Tuple[Any, Any, Dict[str, jax.Array]]:
    with jax.named_scope("optimizer"):
        grads, gnorm = _clip_by_global_norm(grads, tc.grad_clip)
        count = opt_state["count"] + 1
        cf = count.astype(jnp.float32)
        b1, b2 = tc.b1, tc.b2
        lr = lr_at(count, tc)
        bc1 = 1 - b1 ** cf
        bc2 = 1 - b2 ** cf

        def upd(p, g, m, v):
            gf = g.astype(jnp.float32)
            mf = m.astype(jnp.float32) * b1 + gf * (1 - b1)
            vf = v.astype(jnp.float32) * b2 + jnp.square(gf) * (1 - b2)
            step = (mf / bc1) / (jnp.sqrt(vf / bc2) + tc.eps)
            if p.ndim >= 2 and tc.weight_decay:
                step = step + tc.weight_decay * p.astype(jnp.float32)
            newp = p.astype(jnp.float32) - lr * step
            return newp.astype(p.dtype), mf.astype(m.dtype), vf.astype(v.dtype)

        flat_p, td = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_m = jax.tree.leaves(opt_state["m"])
        flat_v = jax.tree.leaves(opt_state["v"])
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = jax.tree.unflatten(td, [o[0] for o in out])
        new_m = jax.tree.unflatten(td, [o[1] for o in out])
        new_v = jax.tree.unflatten(td, [o[2] for o in out])
        metrics = {"grad_norm": gnorm, "lr": lr}
        return new_p, {"m": new_m, "v": new_v, "count": count}, metrics
