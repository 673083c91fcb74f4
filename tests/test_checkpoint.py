"""Fault tolerance: atomic save/restore, async, keep-last GC, torn-write
recovery, elastic re-shard, train-resume continuity."""
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import batch_for, fast_tc, tiny_dense
from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh
from repro.models.api import build_model, init_train_state, make_train_step


def make_state():
    return {"params": {"a": jnp.arange(6.0).reshape(2, 3), "n": {"b": jnp.ones((4,))}},
            "opt": {"count": jnp.zeros((), jnp.int32)}}


def test_save_restore_roundtrip(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    st = make_state()
    cm.save(5, st, meta={"step": 5, "level": 1})
    like = jax.tree.map(jnp.zeros_like, st)
    out, meta = cm.restore(like)
    assert meta["level"] == 1
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(st)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_and_keep_last(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep_last=2)
    st = make_state()
    for s in (1, 2, 3, 4):
        cm.save(s, st, meta={"step": s}, blocking=False)
    cm.wait()
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert dirs == ["step_00000003", "step_00000004"]
    assert cm.latest()["step"] == 4


def test_leaf_names_with_literal_double_underscore(tmp_path):
    """v2 layout: leaf paths are percent-encoded, so a literal ``__`` in a
    leaf name no longer collides with the path separator (the legacy scheme
    mapped both ``w/gate`` and ``w__gate`` to the same file)."""
    cm = CheckpointManager(str(tmp_path))
    st = {"params": {"w__gate": jnp.arange(4.0),
                     "w": {"gate": jnp.full((4,), 7.0)}}}
    cm.save(1, st, meta={"step": 1})
    out, _ = cm.restore(jax.tree.map(jnp.zeros_like, st))
    np.testing.assert_array_equal(np.asarray(out["params"]["w__gate"]),
                                  np.arange(4.0))
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]["gate"]),
                                  np.full((4,), 7.0))


def test_restore_legacy_leaf_layout(tmp_path):
    """Pre-v2 checkpoints ('/' stored as '__', no leafenc marker) stay
    readable."""
    d = tmp_path / "step_00000001" / "params"
    os.makedirs(d)
    np.save(str(d / "a__b.npy"), np.arange(3.0))
    with open(tmp_path / "step_00000001" / "meta.json", "w") as f:
        json.dump({"step": 1}, f)
    with open(tmp_path / "manifest.json", "w") as f:
        json.dump({"dir": "step_00000001", "step": 1, "meta": {"step": 1}}, f)
    cm = CheckpointManager(str(tmp_path))
    out, meta = cm.restore({"params": {"a": {"b": jnp.zeros(3)}}})
    assert meta["step"] == 1
    np.testing.assert_array_equal(np.asarray(out["params"]["a"]["b"]),
                                  np.arange(3.0))


def test_gc_never_removes_manifest_dir(tmp_path):
    """Regression: a resumed run can publish a smaller step number than stale
    dirs from a longer previous schedule.  keep-last GC must never collect the
    directory the manifest references -- and must reclaim the stale
    higher-numbered dirs rather than shield them by name."""
    import time

    cm = CheckpointManager(str(tmp_path), keep_last=1)
    st = make_state()
    cm.save(5, st, meta={"step": 5})
    time.sleep(0.02)  # distinct publish mtimes
    cm.save(3, st, meta={"step": 3})  # lexicographically older than step_5
    m = cm.latest()
    assert m["step"] == 3
    assert os.path.isdir(os.path.join(str(tmp_path), m["dir"]))
    assert not os.path.isdir(os.path.join(str(tmp_path), "step_00000005"))
    out, meta = cm.restore(jax.tree.map(jnp.zeros_like, st))
    assert meta["step"] == 3


def test_torn_manifest_recovery(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    st = make_state()
    cm.save(1, st, meta={"step": 1})
    cm.save(2, st, meta={"step": 2})
    # simulate crash: manifest points at a deleted dir
    with open(cm.manifest_path, "w") as f:
        json.dump({"dir": "step_00000099", "step": 99, "meta": {}}, f)
    m = cm.latest()
    assert m["step"] == 2  # falls back to newest intact step dir


def test_preemption_resume_continuity(tmp_path):
    """Kill training mid-flight; resume must continue bit-identically."""
    cfg = tiny_dense(compute_dtype=jnp.float32)
    tc = fast_tc(steps=6)
    model = build_model(cfg)
    batch = batch_for(cfg)
    step = jax.jit(make_train_step(model, tc))

    params, opt = init_train_state(model, tc, jax.random.PRNGKey(0))
    # uninterrupted run
    p_ref, o_ref = params, opt
    for _ in range(4):
        p_ref, o_ref, _ = step(p_ref, o_ref, batch)

    # interrupted run: 2 steps, checkpoint, "crash", restore, 2 more steps
    cm = CheckpointManager(str(tmp_path))
    p, o = params, opt
    for _ in range(2):
        p, o, _ = step(p, o, batch)
    cm.save(2, {"params": p, "opt": o}, meta={"step": 2})
    del p, o  # crash
    like = {"params": jax.tree.map(jnp.zeros_like, params),
            "opt": jax.tree.map(jnp.zeros_like, opt)}
    restored, meta = cm.restore(like)
    p, o = restored["params"], restored["opt"]
    assert meta["step"] == 2
    for _ in range(2):
        p, o, _ = step(p, o, batch)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=1e-6)


def test_elastic_restore_onto_mesh(tmp_path):
    """Checkpoints hold logical arrays; restore re-shards onto a target mesh
    (different topology than at save time)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    cm = CheckpointManager(str(tmp_path))
    st = {"params": {"w": jnp.arange(16.0).reshape(4, 4)}}
    cm.save(1, st, meta={"step": 1})
    mesh = make_mesh((1, 1), ("data", "model"))  # 1-device container
    sh = {"params": {"w": NamedSharding(mesh, P("data", None))}}
    out, _ = cm.restore(jax.tree.map(jnp.zeros_like, st), shardings=sh)
    assert out["params"]["w"].sharding == sh["params"]["w"]
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.asarray(st["params"]["w"]))
