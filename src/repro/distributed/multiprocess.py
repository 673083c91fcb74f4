"""Multi-process (multi-host) coordination primitives.

Everything here degrades to a no-op / identity in single-process runs, so the
exact same driver code paths serve CPU smoke tests and real multi-host
launches (``jax.distributed.initialize`` lives in ``repro.launch.mesh`` --
see ``init_distributed`` -- because it must run before backend init).

Three multi-process facts the rest of the codebase leans on:

* **Non-addressable arrays cannot be device_put from host data.**  A global
  array sharded (or even just replicated) across processes must be built with
  ``jax.make_array_from_callback`` from each process's addressable slices --
  :func:`put_global` and :func:`GlobalBatchFn` wrap that.
* **Collectives must be called symmetrically.**  Every process must reach the
  same collective in the same order, so coordinated decisions (the preemption
  drain flag) are polled unconditionally once per step on every process --
  :func:`any_process_flag`.
* **Checkpoint publish needs a barrier.**  :func:`barrier` prefers the
  coordination-service barrier (pure RPC, no device computation -- safe to
  call between training steps without interleaving extra collectives) and
  falls back to ``sync_global_devices``.
"""
from __future__ import annotations

import json
from typing import Any, Optional

import jax
import numpy as np

_BARRIER_TIMEOUT_MS = 10 * 60 * 1000


def process_count() -> int:
    return int(jax.process_count())


def process_index() -> int:
    return int(jax.process_index())


def is_primary() -> bool:
    """True on the process that owns logging / watchdog / manifest publish."""
    return process_index() == 0


def _coordination_client():
    try:  # private jax API; None when not distributed
        from jax._src import distributed as _dist

        return _dist.global_state.client
    except Exception:
        return None


def barrier(name: str) -> None:
    """Block until every process reaches this barrier (no-op single-process).

    ``name`` must be unique per synchronization point (the checkpoint manager
    keys it on a per-save sequence number).  Uses the distributed
    coordination-service barrier when available -- a pure RPC, so it cannot
    interleave device collectives with a training step that is still flushing
    -- and falls back to ``multihost_utils.sync_global_devices``.
    """
    if process_count() == 1:
        return
    client = _coordination_client()
    if client is not None:
        client.wait_at_barrier(f"repro:{name}", timeout_in_ms=_BARRIER_TIMEOUT_MS)
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def _require_client():
    client = _coordination_client()
    if client is None:
        raise RuntimeError(
            "the coordination-service KV store needs jax.distributed "
            "(repro.launch.mesh.init_distributed) -- single-process runs "
            "have no peers to exchange with")
    return client


def kv_put(key: str, payload: bytes) -> None:
    """Publish bytes under ``key`` in the coordination-service KV store.

    Keys must be unique per run (callers scope them with per-instance
    sequence counters); values ride the same gRPC channel as barriers, so
    keep them modest (the checkpoint gather moves one leaf chunk at a time).
    """
    _require_client().key_value_set_bytes(f"repro:{key}", payload)


def kv_fetch(key: str, timeout_ms: int = _BARRIER_TIMEOUT_MS) -> bytes:
    """Block until some process ``kv_put``s ``key``; returns its bytes."""
    return _require_client().blocking_key_value_get_bytes(
        f"repro:{key}", timeout_ms)


def kv_delete(key: str) -> None:
    """Best-effort delete of a KV entry.

    The coordinator holds every key in RAM for the life of the job, so
    producers MUST clean up once all consumers are provably past their
    fetches (i.e. after a barrier) -- a days-long run checkpointing on a
    cadence would otherwise grow coordinator memory without bound.  Failures
    are swallowed: a leaked key is a leak, not a correctness problem.
    """
    try:
        _require_client().key_value_delete(f"repro:{key}")
    except Exception:
        pass


def _kv_chunk_bytes() -> int:
    """Max bytes per KV message (env-tunable; tests shrink it to force
    multi-part streams)."""
    import os

    return max(1, int(os.environ.get("REPRO_KV_CHUNK_BYTES", 2 * 1024 * 1024)))


# Every stream message is prefixed so it can never be shorter than 2 bytes:
# this jaxlib's coordination service SEGFAULTS the whole job on a blocking
# get of a 1-byte value (empirically: 1-byte crashes, >=2 bytes are fine).
_STREAM_PREFIX = b"P:"


def kv_put_stream(key: str, payload: bytes) -> None:
    """Publish arbitrarily large bytes under ``key`` as bounded chunks.

    The coordination service rides gRPC, whose default message cap is ~4MB --
    one-message-per-leaf-chunk (`kv_put`) breaks on large checkpoint leaves.
    Payloads are split into ``REPRO_KV_CHUNK_BYTES``-sized parts
    (``{key}/part{i}``); the part count lands LAST under ``{key}/meta``, so a
    blocked :func:`kv_fetch_stream` that sees the meta is guaranteed every
    part is already published.
    """
    chunk = _kv_chunk_bytes()
    n = max(1, -(-len(payload) // chunk))
    for i in range(n):
        kv_put(f"{key}/part{i}",
               _STREAM_PREFIX + payload[i * chunk:(i + 1) * chunk])
    kv_put(f"{key}/meta", f"n={n}".encode())


def kv_fetch_stream(key: str, timeout_ms: int = _BARRIER_TIMEOUT_MS) -> bytes:
    """Block until :func:`kv_put_stream` publishes ``key``; reassembles the
    parts in order."""
    meta = kv_fetch(f"{key}/meta", timeout_ms)
    n = int(meta.decode().split("=", 1)[1])
    return b"".join(kv_fetch(f"{key}/part{i}", timeout_ms)[len(_STREAM_PREFIX):]
                    for i in range(n))


def kv_delete_stream(key: str) -> None:
    """Best-effort cleanup of a streamed key (same contract as
    :func:`kv_delete`: call only after consumers are provably past their
    fetches)."""
    try:
        meta = kv_fetch(f"{key}/meta", timeout_ms=1000)
        n = int(meta.decode().split("=", 1)[1])
    except Exception:
        return
    for i in range(n):
        kv_delete(f"{key}/part{i}")
    kv_delete(f"{key}/meta")


def kv_allgather(tag: str, payload: bytes,
                 timeout_ms: int = _BARRIER_TIMEOUT_MS) -> list:
    """Every process contributes ``payload`` under ``tag``; returns the list
    of all processes' payloads, rank-ordered and identical everywhere.

    Holds the exchange choreography in ONE place: put, fetch-all, barrier
    (proving every consumer is past its fetches), then a rank-0 cleanup sweep
    so the coordinator's RAM is reclaimed.  ``tag`` must be unique per
    exchange (callers scope it with per-instance sequence counters), and the
    call is a collective -- every process must reach it with the same tag.
    """
    pid, n = process_index(), process_count()
    kv_put(f"{tag}-{pid}", payload)
    out = [kv_fetch(f"{tag}-{r}", timeout_ms) for r in range(n)]
    barrier(f"{tag}-ag")
    if pid == 0:
        for r in range(n):
            kv_delete(f"{tag}-{r}")
    return out


def kv_json_allgather(tag: str, obj: Any,
                      timeout_ms: int = _BARRIER_TIMEOUT_MS) -> list:
    """:func:`kv_allgather` for JSON-serializable objects.

    Every process contributes ``obj``; returns all processes' decoded
    objects, rank-ordered and identical everywhere.  The checkpoint manager's
    control-plane exchanges (latest-candidate election, per-host manifest
    index merge, have/want object negotiation) all ride this.
    """
    return [json.loads(p) for p in
            kv_allgather(tag, json.dumps(obj).encode(), timeout_ms)]


def any_process_flag(flag: bool) -> bool:
    """Cross-process OR of a host-side flag (identity single-process).

    This is a collective: in multi-process runs EVERY process must call it at
    the same point (the drivers poll it exactly once per training step), which
    is also what makes the result well-defined -- all processes see the same
    answer at the same step, so e.g. a SIGTERM delivered to one process drains
    the whole job at one agreed step boundary.
    """
    if process_count() == 1:
        return bool(flag)
    from jax.experimental import multihost_utils

    got = multihost_utils.process_allgather(
        np.asarray([1 if flag else 0], np.int32))
    return bool(np.asarray(got).sum() > 0)


def put_global(x: Any, sharding) -> jax.Array:
    """``jax.device_put`` that also works when ``sharding`` spans processes.

    The caller must hold the FULL logical value on every process (true for
    deterministic inits, host-regenerated batches and reassembled checkpoint
    leaves); each process materializes only its addressable shards.
    """
    if sharding is None:
        return x
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(x, sharding)
    host = np.asarray(jax.device_get(x))
    return jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx])


def put_global_tree(tree, shardings):
    """Tree version of :func:`put_global` (``shardings=None`` -> identity)."""
    if shardings is None:
        return tree
    return jax.tree.map(put_global, tree, shardings)


class GlobalBatchFn:
    """Wrap a host-batch fn for a mesh that spans processes.

    The global batch is process-count-invariant: every process regenerates THE
    canonical batch for a step deterministically (``data/synthetic``: batches
    are pure functions of (seed, step, shard), so any host can do this) and
    materializes only the rows its data-axis coordinate addresses
    (``distributed.data_shard_index`` names that slice).  A 2-process
    ``--mesh 2x1`` run therefore consumes exactly the same data stream as a
    1-process run -- which is what makes cross-process-count resume and the
    equivalence tests well-posed.

    ``like`` exposes the batch's ShapeDtypeStruct tree without tracing through
    the host->global conversion (``jax.eval_shape`` cannot, because the
    conversion calls ``device_get``).
    """

    def __init__(self, batch_fn, mesh, rules=None):
        from repro.distributed.sharding import batch_shardings

        self.inner = batch_fn
        self.mesh = mesh
        self.like = jax.eval_shape(batch_fn, 0)
        self.shardings = batch_shardings(self.like, mesh, rules)

    def __call__(self, step):
        host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                            self.inner(step))
        return jax.tree.map(
            lambda x, s: jax.make_array_from_callback(
                x.shape, s, lambda idx, x=x: x[idx]),
            host, self.shardings)


def as_global_batch_fn(batch_fn, mesh: Optional[Any], rules=None):
    """Multi-process-safe batch fn (identity when one process or no mesh)."""
    if mesh is None or process_count() == 1:
        return batch_fn
    return GlobalBatchFn(batch_fn, mesh, rules)


class FusedDrainFlag:
    """Preemption drain flag fused into the compiled train step.

    The dedicated per-step ``process_allgather`` of the SIGTERM flag (a tiny
    host-side gloo round-trip between every step) is replaced by one extra
    input/output on the step itself: each process authors one int32 element
    per device it owns in a mesh-shaped array (``device_flag``), the step
    reduces it with ``jnp.max`` into a replicated ``metrics["drain"]`` scalar,
    and the cross-process OR therefore rides the step's existing collective
    schedule -- XLA fuses and overlaps it with the step's other reductions
    instead of a separate synchronous RPC.

    Wiring (see ``launch/train.py`` / ``core/vcycle.py``): the driver attaches
    an instance to its ``PreemptionGuard``; every step feeds
    ``device_flag()`` in and hands ``metrics["drain"]`` to ``observe``;
    ``PreemptionGuard.should_stop`` then reads ``last()`` instead of
    all-gathering.  Each element is single-authored by the process owning its
    device, so every process computes the identical ``max`` at the identical
    step -- a notice delivered to ANY ONE process still drains the whole job
    at one agreed step boundary (pinned by tests/test_multiprocess.py).
    """

    def __init__(self, mesh, guard=None):
        from jax.sharding import NamedSharding, PartitionSpec

        self.mesh = mesh
        self.guard = guard  # anything with a host-side ``triggered`` bool
        self.shape = tuple(np.shape(mesh.devices))
        # fully partitioned over every mesh axis: one element per device,
        # each authored only by the process that owns that device (a
        # replicated spec would let processes disagree about replica values)
        self.sharding = NamedSharding(mesh, PartitionSpec(*mesh.axis_names))
        self._last = None

    def device_flag(self) -> jax.Array:
        """This step's flag input: my devices' elements carry MY flag."""
        v = 1 if (self.guard is not None
                  and getattr(self.guard, "triggered", False)) else 0

        def shard(idx):
            dims = [len(range(*sl.indices(dim)))
                    for sl, dim in zip(idx, self.shape)]
            return np.full(dims, v, np.int32)

        return jax.make_array_from_callback(self.shape, self.sharding, shard)

    @staticmethod
    def reduce(flag: jax.Array) -> jax.Array:
        """The in-step cross-device OR (inside jit, alongside the metrics)."""
        import jax.numpy as jnp

        return jnp.max(flag)

    def wrap_step(self, step, *, in_shardings, out_shardings,
                  donate_argnums=(0, 1)):
        """jit an n-ary ``step(*state, batch) -> (*state, metrics)`` with the
        drain flag fused in: the compiled step takes the flag as an extra
        input, emits the replicated ``metrics["drain"]`` scalar, and the
        returned wrapper feeds/observes it transparently -- call sites keep
        the step's own signature.  Both drivers share this wiring (the
        classic step is 3-ary; the grad-reduce step threads its EF state as a
        4th state leg)."""

        def fused(*args):
            *inputs, flag = args
            *outs, m = step(*inputs)
            m = dict(m)
            # the cross-process preemption OR rides the step's own
            # collective schedule (no dedicated per-step allgather)
            m["drain"] = self.reduce(flag)
            return (*outs, m)

        fused.__name__ = fused.__qualname__ = step.__name__  # names the program
        compiled = jax.jit(fused,
                           in_shardings=(*in_shardings, self.sharding),
                           out_shardings=out_shardings,
                           donate_argnums=donate_argnums)

        def fn(*args):
            out = compiled(*args, self.device_flag())
            self.observe(out[-1]["drain"])
            return out

        return fn

    def observe(self, drain) -> None:
        """Record the step's replicated drain scalar (device value; the host
        read is deferred to ``last`` so pipelining is preserved)."""
        self._last = drain

    def last(self) -> bool:
        """True when any process's flag was set as of the last observed step."""
        return self._last is not None and int(jax.device_get(self._last)) > 0


def batch_like(batch_fn):
    """ShapeDtypeStruct tree for ``batch_fn`` -- honors a precomputed
    ``.like`` (set by :class:`GlobalBatchFn`, whose host->global conversion
    cannot be traced by ``jax.eval_shape``)."""
    like = getattr(batch_fn, "like", None)
    return like if like is not None else jax.eval_shape(batch_fn, 0)
