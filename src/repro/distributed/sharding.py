"""Logical-axis -> mesh-axis sharding rules.

A single rules table maps every logical axis name (the same names used by the
coalescing operators) to mesh axes.  ``spec_for`` drops any mapping whose size
does not divide the mesh axis product (e.g. 40 heads on a 16-way model axis,
batch=1 decode) so every architecture lowers cleanly; what gets dropped is
visible in the roofline report as a replicated (memory-heavier) term.

Layers call ``shard_l(x, axes)`` which is a no-op outside a mesh context, so
smoke tests on CPU run the exact same model code.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import param as pm

AxisMap = Union[None, str, Tuple[str, ...]]

# fsdp axes: the data-like axes used for parameter (ZeRO-3 style) sharding.
# They are resolved per-mesh: ("pod","data") when a "pod" axis exists.
FSDP = "__fsdp__"
DP = "__dp__"  # all data-like axes, for activation batch dims

RULES: Dict[str, AxisMap] = {
    # --- parameter axes ---
    "embed": FSDP,           # residual stream width: FSDP-sharded on params
    "embed_cat2": FSDP,
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",      # expert parallelism
    "moe_mlp": None,
    "shared_mlp": "model",
    "q_lora": None,
    "kv_lora": None,
    "head_dim": None,
    "v_head_dim": None,
    "rope_dim": None,
    "layers": None,
    "mamba_inner": "model",
    "mamba_state": None,
    "dt_rank": None,
    "conv_k": None,
    "xlstm_inner": "model",
    "vision_embed": None,
    "classes": None,
    "patch": None,
    "mtp": None,
    # --- activation axes ---
    "batch": DP,
    "seq": None,
    "act_embed": None,       # residual activations replicated over "model" (TP)
    "act_heads": "model",
    "act_kv_heads": "model",
    "act_mlp": "model",
    "act_experts": "model",
    "act_experts_mid": "model",  # intermediate hop for the EP reshard (serving)
    "moe_batch": DP,         # batch dim inside expert compute (None when serving)
    "act_vocab": "model",
    "act_mamba": "model",
    "act_xlstm": "model",
    "cache_seq": "model",    # decode KV/latent caches: sequence-sharded (flash-decode CP)
    "attn_seq": "model",     # context-parallel attention activations (opt-in)
    "cache_kv_heads": None,
    "capacity": None,
    "img_seq": None,
    "enc_seq": None,
}

# Serving-time overrides: parameters are read-only (no optimizer state), so
# FSDP gathering them every decode step is pure waste.  Experts spread over
# the FULL device set (256-way EP: DeepSeek-V3 fits at ~88MB/expert/device)
# and the remaining weights replicate over the data axis, ending the
# per-token parameter all-gathers (EXPERIMENTS.md §Perf deepseek iter.2).
SERVE_RULES: Dict[str, AxisMap] = {
    # model-major expert placement: the (batch:data -> experts:data) reshard
    # then factors as a clean all-to-all over "data" instead of GSPMD's
    # replicate-and-repartition fallback (measured: 2x1.9GB AG per MoE layer)
    "experts": ("model", "data"),
    "act_experts": ("model", "data"),  # expert compute spread over ALL devices
    "moe_batch": None,  # ...with the token dim replicated inside the a2a region
    # few-expert models (jamba/phi: 16 experts -> the progressive drop lands
    # them on "data") shard the expert HIDDEN dim over the leftover "model"
    # axis -- without this jamba-1.5-large serving holds 44 GB of expert FFNs
    # per device; deepseek (256-way expert sharding) drops this mapping.
    "moe_mlp": "model",
    "embed": None,
    "embed_cat2": None,
}

_CTX: dict = {"mesh": None, "rules": None, "extra": None}


def _resolve(rules: Dict[str, AxisMap], mesh: Mesh, name: str) -> Tuple[str, ...]:
    m = rules.get(name, None)
    if m is None:
        return ()
    if m == FSDP:
        return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if m == DP:
        return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if isinstance(m, str):
        return (m,) if m in mesh.axis_names else ()
    return tuple(a for a in m if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def logical_spec(
    shape: Sequence[int],
    axes: Sequence[str],
    mesh: Mesh,
    rules: Optional[Dict[str, AxisMap]] = None,
) -> P:
    """PartitionSpec for a tensor with logical axes; drops non-divisible mappings
    and never assigns the same mesh axis twice."""
    rules = rules or RULES
    used: set = set()
    entries = []
    for dim, name in zip(shape, axes):
        cand = _resolve(rules, mesh, name)
        cand = tuple(a for a in cand if a not in used)
        # progressively drop leading axes until the dim divides (e.g. 16
        # experts on a ("data","model") 256-way serving map -> ("model",))
        while cand and dim % _axis_size(mesh, cand) != 0:
            cand = cand[1:]
        if cand:
            used.update(cand)
            entries.append(cand if len(cand) > 1 else cand[0])
        else:
            entries.append(None)
    return P(*entries)


def set_mesh_ctx(mesh: Mesh, rules: Optional[Dict[str, AxisMap]] = None) -> None:
    _CTX["mesh"] = mesh
    _CTX["rules"] = dict(RULES, **(rules or {}))


def clear_mesh_ctx() -> None:
    _CTX["mesh"] = None
    _CTX["rules"] = None


@contextlib.contextmanager
def mesh_ctx(mesh: Mesh, rules: Optional[Dict[str, AxisMap]] = None):
    """Enter mesh: layer-level ``shard_l`` constraints become active, and
    Pallas kernels run per shard (``kernels/dispatch.py``).  No jax-level
    mesh is set: every constraint names its mesh in a ``NamedSharding``, and
    arrays created inside (batches, projection matrices) stay uncommitted,
    so jit places them by its ``in_shardings``."""
    prev = (_CTX["mesh"], _CTX["rules"])
    set_mesh_ctx(mesh, rules)
    try:
        yield mesh
    finally:
        _CTX["mesh"], _CTX["rules"] = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX["mesh"]


def current_rules() -> Dict[str, AxisMap]:
    return _CTX["rules"] or RULES


@contextlib.contextmanager
def no_constraints():
    """Suspend ``shard_l`` constraints (trace-time).

    Inside a ``shard_map`` body the mesh axes are already bound manually, so
    GSPMD sharding constraints are meaningless (and jax rejects
    with_sharding_constraint against the same mesh's axes there).  The
    shard_map'd train step wraps its forward/backward in this.
    """
    prev = (_CTX["mesh"], _CTX["rules"])
    _CTX["mesh"], _CTX["rules"] = None, None
    try:
        yield
    finally:
        _CTX["mesh"], _CTX["rules"] = prev


def shard_l(x: jax.Array, axes: Sequence[str], overrides: Optional[Dict] = None) -> jax.Array:
    """Apply a logical sharding constraint; no-op outside a mesh context."""
    mesh = _CTX["mesh"]
    if mesh is None:
        return x
    rules = dict(_CTX["rules"], **overrides) if overrides else _CTX["rules"]
    spec = logical_spec(x.shape, axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_shardings(specs, mesh: Mesh, rules=None):
    """NamedSharding tree for a Spec tree (params / optimizer / cache)."""

    def one(s: pm.Spec):
        return NamedSharding(mesh, logical_spec(s.shape, s.axes, mesh, rules))

    return jax.tree.map(one, specs, is_leaf=pm.is_spec)


def activation_spec(shape, axes, mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(shape, axes, mesh, rules))


def batch_shardings(batch_like, mesh: Mesh, rules=None):
    """Data-parallel NamedSharding tree for a batch pytree.

    Every leaf's leading dim is the logical "batch" axis (sharded over the
    data-like mesh axes when divisible, replicated otherwise -- same
    progressive-drop rule as parameters); trailing dims replicate.  Accepts
    concrete arrays or ShapeDtypeStructs (e.g. ``jax.eval_shape(batch_fn, 0)``).
    """

    def one(x):
        axes = ("batch",) + ("seq",) * (len(x.shape) - 1)
        return NamedSharding(mesh, logical_spec(x.shape, axes, mesh, rules))

    return jax.tree.map(one, batch_like)


def data_shard_index(mesh: Optional[Mesh] = None) -> int:
    """Deterministic data-shard id for THIS process (feeds ``make_batch_fn``).

    Without a mesh this is ``jax.process_index()``.  With a mesh it is the
    coordinate of the process's first local device along the data-like
    ("pod", "data") axes, flattened -- model-parallel co-hosts share a shard
    while data-parallel hosts get distinct ones.  Single-process runs (CPU
    tests, smoke) always map to shard 0, keeping batches identical across
    mesh shapes so cross-mesh resume equivalence is well-posed.
    """
    if mesh is None:
        return int(jax.process_index())
    if jax.process_count() == 1:
        return 0
    local = {d.id for d in jax.local_devices()}
    dev = np.asarray(mesh.devices)
    data_dims = [i for i, a in enumerate(mesh.axis_names) if a in ("pod", "data")]
    for idx in np.ndindex(dev.shape):
        if dev[idx].id in local:
            shard = 0
            for i in data_dims:
                shard = shard * dev.shape[i] + idx[i]
            return shard
    return int(jax.process_index())
