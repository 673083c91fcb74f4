"""Kernel backend registry + dispatch layer.

Every compute hot-spot the paper optimizes (``flash_attention``,
``coalesce_pair``, ``interp_axpy``), the short-sequence ``attention_tile``
(rotary + attention in one tile) and the serving-side
``paged_attention_decode`` (block-table KV gather) is registered under three
backends:

  * ``pallas``           -- the real Mosaic TPU kernel (TPU hardware only)
  * ``pallas-interpret`` -- the same kernel body executed by the Pallas
                            interpreter (CPU validation; bit-exact semantics,
                            not a performance path)
  * ``xla``              -- a matrix-free pure-jnp reference that lowers for
                            any backend

Selection order (first hit wins):

  1. an explicit ``backend=`` argument (``ModelConfig.kernel_backend`` is
     threaded here by the layers and operators),
  2. the ``REPRO_KERNEL_BACKEND`` environment variable,
  3. the platform default: ``pallas`` on TPU, ``xla`` elsewhere.

Requesting ``pallas`` off-TPU auto-downgrades to ``pallas-interpret`` (Mosaic
cannot compile on CPU; this keeps CPU tests on the kernel bodies).  On TPU
everything resolves exactly as asked, and a ``pallas`` op always runs its
kernel: shapes the tiles do not divide are padded, never handed to XLA.
Resolution happens at trace time, so a jitted caller bakes the chosen backend
into its executable -- no host round-trips inside ``vcycle`` level
transitions.
"""
from __future__ import annotations

import collections
import functools
import os
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh, current_rules, logical_spec
from repro.kernels import attention_tile as tile_lib
from repro.kernels import ref
from repro.kernels.coalesce_pair import coalesce_pair
from repro.kernels.flash_attention import flash_attention_with_vjp
from repro.kernels.interp_axpy import interp_axpy
from repro.kernels.paged_attention import paged_attention_decode

BACKENDS = ("pallas", "pallas-interpret", "xla")
ENV_VAR = "REPRO_KERNEL_BACKEND"

_REGISTRY: Dict[str, Dict[str, Callable]] = {}
# trace-time record of the implementation each op lowered to.  A jitted
# caller bakes the resolved backend into its executable, so this is what a
# compiled step runs (chip_smoke.py prints it and refuses an XLA fallback).
_TRACED: collections.Counter = collections.Counter()


def register(op: str, backend: str, fn: Callable, *, override: bool = False) -> None:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    impls = _REGISTRY.setdefault(op, {})
    if backend in impls and not override:
        raise ValueError(f"{op}/{backend} already registered")
    impls[backend] = fn


def ops() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def backends(op: str) -> Tuple[str, ...]:
    if op not in _REGISTRY:
        raise KeyError(f"unknown op {op!r}; registered: {ops()}")
    return tuple(b for b in BACKENDS if b in _REGISTRY[op])


def validate_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; expected one of {BACKENDS}")
    return backend


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_backend() -> str:
    return "pallas" if on_tpu() else "xla"


def resolve_backend(op: str, backend: Optional[str] = None,
                    default: Optional[str] = None) -> str:
    """Resolve the backend name for ``op`` (argument > env > default >
    platform).  ``default`` lets a caller state its own preference (e.g.
    ``attn_impl="pallas"`` prefers pallas) without shadowing the user's
    explicit config/env choice."""
    b = backend or os.environ.get(ENV_VAR) or default or default_backend()
    validate_backend(b)
    if b == "pallas" and not on_tpu():
        b = "pallas-interpret"
    if b not in _REGISTRY.get(op, {}):
        raise KeyError(f"op {op!r} has no {b!r} implementation "
                       f"(available: {backends(op)})")
    return b


def get_impl(op: str, backend: str) -> Callable:
    if op not in _REGISTRY or backend not in _REGISTRY[op]:
        raise KeyError(f"no implementation for {op!r}/{backend!r}")
    note(op, backend)
    return _REGISTRY[op][backend]


def note(op: str, backend: str) -> None:
    """Record that ``op`` was traced with ``backend`` (including a layer's
    own fall-through to an XLA recipe outside the registry)."""
    _TRACED[(op, backend)] += 1


def traced() -> Dict[Tuple[str, str], int]:
    """``{(op, backend): times traced}`` since the last :func:`reset_traced`."""
    return dict(_TRACED)


def reset_traced() -> None:
    _TRACED.clear()


def dispatch(op: str, *args, backend: Optional[str] = None, **kw):
    """Resolve and call ``op``.  Safe inside jit: resolution is trace-time."""
    return get_impl(op, resolve_backend(op, backend))(*args, **kw)


# ---------------------------------------------------------------------------
# registered implementations
#
# All backends of one op share a single keyword signature so callers (layers,
# operators, benchmarks, tests) can swap backends without code changes.
#
# XLA cannot partition a Mosaic kernel, so under a mesh (``mesh_ctx``) every
# Pallas implementation runs per shard inside ``jax.shard_map``; each op
# names how its operands split (``_per_shard``).


def _per_shard(specs: Callable):
    """Run the kernel per shard of the context mesh.  ``specs(mesh, rules,
    *args, **kw) -> (in_specs, out_specs)`` covers the leading array
    arguments; the positional ones after them are static.  Without a mesh
    (or on one device) the kernel is called as is."""

    def wrap(kernel):
        @functools.wraps(kernel)
        def call(*args, **kw):
            mesh = current_mesh()
            if mesh is None or mesh.size == 1:
                return kernel(*args, **kw)
            in_specs, out_specs = specs(mesh, current_rules(), *args, **kw)
            n = len(in_specs)
            return jax.shard_map(
                lambda *arrays: kernel(*arrays, *args[n:], **kw), mesh=mesh,
                in_specs=in_specs, out_specs=out_specs,
                check_vma=False)(*args[:n])
        return call

    return wrap


def _split_one_dim(shape, mesh) -> P:
    """Every mesh axis on the first dim they divide (replicated if none):
    the layout for ops that are elementwise along that dim."""
    for d, n in enumerate(shape):
        if n % mesh.size == 0:
            return P(*([None] * d), tuple(mesh.axis_names))
    return P()


def _flash_specs(mesh, rules, q, k, v, **_):
    spec = logical_spec(q.shape, ("batch", "act_heads", "seq", "head_dim"),
                        mesh, rules)
    return (spec, spec, spec), spec


def _tile_specs(mesh, rules, q, k, v, positions, *, head_dim, **_):
    """Split rows over the batch axes and the merged ``H*D`` lanes on head
    boundaries, where each shard's heads still fit the kernel's lanes."""
    B, S, HD = q.shape
    heads = HD // head_dim
    spec = logical_spec((B, S, heads, head_dim),
                        ("batch", "seq", "act_heads", "head_dim"), mesh, rules)
    split = spec[2]
    n = 1
    for a in (split if isinstance(split, tuple) else (split,) if split else ()):
        n *= mesh.shape[a]
    if not tile_lib.fits(S, heads // n, head_dim):
        split = None
    rows = P(spec[0], None, split)
    return (rows, rows, rows, P(spec[0], None)), rows


def _coalesce_specs(mesh, rules, w, *, axis, **_):
    other = list(w.shape)
    other[axis] = 1  # the paired dim stays whole on every shard
    spec = _split_one_dim(other, mesh)
    return (spec,), spec


def _axpy_specs(mesh, rules, a, b, alpha, **_):
    spec = _split_one_dim(a.shape, mesh)
    return (spec, spec), spec


def _paged_specs(mesh, rules, q, k_pages, v_pages, block_tables, lengths, **_):
    pool = logical_spec(k_pages.shape,
                        ("pages", "cache_kv_heads", "page_seq", "head_dim"),
                        mesh, rules)
    heads = P(None, pool[1], None, None)  # q/out rows follow the pool's heads
    return (heads, pool, pool, P(), P()), heads


@_per_shard(_flash_specs)
def _flash_attention_pallas(q, k, v, *, causal=True, scale=None,
                            block_q=128, block_k=128, interpret=False):
    return flash_attention_with_vjp(q, k, v, causal=causal, scale=scale,
                                    block_q=block_q, block_k=block_k,
                                    interpret=interpret)


def _flash_attention_interpret(q, k, v, *, causal=True, scale=None,
                               block_q=128, block_k=128):
    return _flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   interpret=True)


def _flash_attention_xla(q, k, v, *, causal=True, scale=None,
                         block_q=0, block_k=0):
    return ref.naive_attention(q, k, v, causal=causal, scale=scale)


@_per_shard(_tile_specs)
def _attention_tile_pallas(q, k, v, positions, *, head_dim, causal, scale,
                           theta, interpret=False):
    return tile_lib.attention_tile(q, k, v, positions, head_dim=head_dim,
                                   causal=causal, scale=scale, theta=theta,
                                   interpret=interpret)


def _attention_tile_interpret(q, k, v, positions, *, head_dim, causal, scale,
                              theta):
    return _attention_tile_pallas(q, k, v, positions, head_dim=head_dim,
                                  causal=causal, scale=scale, theta=theta,
                                  interpret=True)


def _attention_tile_xla(q, k, v, positions, *, head_dim, causal, scale, theta):
    """The reference: ``apply_rope`` + ``plain_attention`` on [B,S,H,D]."""
    from repro.layers.attention import plain_attention  # imports this module
    from repro.layers.basic import apply_rope

    B, S, HD = q.shape
    heads = (B, S, HD // head_dim, head_dim)
    qr = apply_rope(q.reshape(heads), positions, theta)
    kr = apply_rope(k.reshape(heads), positions, theta)
    out = plain_attention(qr[:, :, :, None], kr, v.reshape(heads),
                          causal=causal, scale=scale)
    return out.reshape(B, S, HD)


def coalesce_pair_xla(w, *, axis: int, w0: float = 0.5, block: int = 0):
    """Matrix-free XLA reference: one fused slice+add pass, any ndim."""
    n = w.shape[axis]
    if n % 2:
        raise ValueError(f"axis {axis} size {n} must be even")
    half = n // 2
    a = jax.lax.slice_in_dim(w, 0, half, axis=axis)
    b = jax.lax.slice_in_dim(w, half, n, axis=axis)
    return (w0 * (a.astype(jnp.float32) + b.astype(jnp.float32))).astype(w.dtype)


@_per_shard(_coalesce_specs)
def _coalesce_pair_pallas(w, *, axis, w0=0.5, block=512, interpret=False):
    return coalesce_pair(w, axis=axis, w0=w0, block=block, interpret=interpret)


def _coalesce_pair_interpret(w, *, axis, w0=0.5, block=512):
    return _coalesce_pair_pallas(w, axis=axis, w0=w0, block=block, interpret=True)


@_per_shard(_axpy_specs)
def _interp_axpy_pallas(a, b, alpha, *, block=1024, interpret=False):
    return interp_axpy(a, b, alpha, block=block, interpret=interpret)


def _interp_axpy_interpret(a, b, alpha, *, block=1024):
    return _interp_axpy_pallas(a, b, alpha, block=block, interpret=True)


def _interp_axpy_xla(a, b, alpha, *, block=0):
    return ref.interp_axpy_ref(a, b, alpha)


@_per_shard(_paged_specs)
def _paged_attention_pallas(q, k_pages, v_pages, block_tables, lengths, *,
                            scale=None, interpret=False):
    return paged_attention_decode(q, k_pages, v_pages, block_tables, lengths,
                                  scale=scale, interpret=interpret)


def _paged_attention_interpret(q, k_pages, v_pages, block_tables, lengths, *,
                               scale=None):
    return _paged_attention_pallas(q, k_pages, v_pages, block_tables, lengths,
                                   scale=scale, interpret=True)


def _paged_attention_xla(q, k_pages, v_pages, block_tables, lengths, *,
                         scale=None):
    # the pool is head-major [N, KH, P, D]; the oracle reads [N, P, KH, D]
    return ref.paged_attention_ref(q, jnp.swapaxes(k_pages, 1, 2),
                                   jnp.swapaxes(v_pages, 1, 2), block_tables,
                                   lengths, scale=scale)


register("flash_attention", "pallas", _flash_attention_pallas)
register("flash_attention", "pallas-interpret", _flash_attention_interpret)
register("flash_attention", "xla", _flash_attention_xla)

register("attention_tile", "pallas", _attention_tile_pallas)
register("attention_tile", "pallas-interpret", _attention_tile_interpret)
register("attention_tile", "xla", _attention_tile_xla)

register("coalesce_pair", "pallas", _coalesce_pair_pallas)
register("coalesce_pair", "pallas-interpret", _coalesce_pair_interpret)
register("coalesce_pair", "xla", coalesce_pair_xla)

register("interp_axpy", "pallas", _interp_axpy_pallas)
register("interp_axpy", "pallas-interpret", _interp_axpy_interpret)
register("interp_axpy", "xla", _interp_axpy_xla)

register("paged_attention_decode", "pallas", _paged_attention_pallas)
register("paged_attention_decode", "pallas-interpret", _paged_attention_interpret)
register("paged_attention_decode", "xla", _paged_attention_xla)
