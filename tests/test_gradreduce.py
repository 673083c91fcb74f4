"""Pluggable gradient reduction (distributed/reduce.py) end to end.

Fast single-device tests pin the mechanics: the packed ef_int8_psum payload
(ONE pmax + ONE psum for the whole tree), the dense shard_map step's
equivalence to the legacy pjit step, the strategy factory, wire-bytes
accounting, the EF-state lifecycle through V-cycle checkpoints (reset at
level transitions, restore-without-strategy fails loudly), the KV streaming
framing and the sharding-aware restore geometry.

Slow 2-process drills pin the acceptance criteria: an int8_ef V-cycle over a
real ("pod","data","model") mesh executes ef_int8_psum inside the compiled
step (call probe, not config), tracks the dense loss trajectory within
tolerance, and survives kill-and-resume with the EF residuals intact.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from helpers import mp_arena, run_multiprocess, tiny_dense, fast_tc, batch_for
from repro.distributed.compression import (dense_wire_bytes, ef_compress,
                                           ef_int8_psum, ef_psum_calls,
                                           init_ef_state, int8_wire_bytes,
                                           reset_ef_psum_probe)
from repro.distributed.reduce import (DenseReduce, HierarchicalInt8EF,
                                      make_grad_reduce)
from repro.launch.mesh import make_mesh


@pytest.fixture(autouse=True)
def _fresh_probe():
    reset_ef_psum_probe()
    yield
    reset_ef_psum_probe()


def _flat(tree):
    from repro.checkpoint.manager import _flatten

    return _flatten(jax.device_get(tree))


def _assert_trees(a, b, atol, err=""):
    a, b = _flat(a), _flat(b)
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k], np.float64),
                                   np.asarray(b[k], np.float64),
                                   atol=atol, err_msg=f"{err}:{k}")


# ---------------------------------------------------------------------------
# packed compression payload


def _shardmap_psum(grads, ef):
    mesh = make_mesh((1,), ("pod",))
    return jax.jit(jax.shard_map(
        lambda g, e: ef_int8_psum(g, e, "pod"), mesh=mesh,
        in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False))(grads, ef)


def test_packed_psum_matches_per_leaf_reference():
    """On a 1-rank axis the packed path must agree leaf-for-leaf with the
    reference ``ef_compress`` (pmax of one rank == the local scale, so the
    quantization decisions are identical)."""
    key = jax.random.PRNGKey(0)
    grads = {"a": jax.random.normal(key, (16, 8)) * 0.3,
             "b": jax.random.normal(jax.random.PRNGKey(1), (32,)) * 2.0,
             "c": jax.random.normal(jax.random.PRNGKey(2), (4, 4, 4)) * 1e-3}
    ef = jax.tree.map(lambda g: jnp.abs(g) * 0.01, grads)
    out, new_ef = _shardmap_psum(grads, ef)
    for k in grads:
        q, s, ref_ef = ef_compress(grads[k], ef[k])
        np.testing.assert_allclose(np.asarray(out[k]),
                                   np.asarray(q, np.float32) * float(s),
                                   rtol=1e-5, atol=1e-6, err_msg=k)
        np.testing.assert_allclose(np.asarray(new_ef[k]), np.asarray(ref_ef),
                                   atol=1e-6, err_msg=k)


def test_packed_psum_conserves_signal():
    """EF identity through the packed path: sent + carried == grad + carry-in
    to f32 roundoff, for every leaf."""
    grads = {"w": jax.random.normal(jax.random.PRNGKey(3), (64,)) * 0.05,
             "v": jax.random.normal(jax.random.PRNGKey(4), (8, 8)) * 7.0}
    ef = init_ef_state(grads)
    out, new_ef = _shardmap_psum(grads, ef)
    for k in grads:
        np.testing.assert_allclose(np.asarray(out[k] + new_ef[k]),
                                   np.asarray(grads[k]), atol=1e-5, err_msg=k)


def test_packed_psum_is_two_collectives_total():
    """The whole point of packing: 2 collectives per step (one pmax over the
    stacked scales + one int32 psum over the concatenated payload) instead of
    2 per leaf."""
    mesh = make_mesh((1,), ("pod",))
    grads = {f"l{i}": jnp.ones((4, 4)) for i in range(5)}
    ef = init_ef_state(grads)
    f = jax.shard_map(lambda g, e: ef_int8_psum(g, e, "pod"), mesh=mesh,
                      in_specs=(P(), P()), out_specs=(P(), P()),
                      check_vma=False)
    text = str(jax.make_jaxpr(f)(grads, ef))
    assert text.count("psum") == 1, text
    assert text.count("pmax") == 1, text


def test_wire_bytes_ratio_at_least_3x():
    grads = {"emb": jnp.zeros((128, 32)), "w": jnp.zeros((32, 64)),
             "b": jnp.zeros((64,))}
    dense = DenseReduce(data_axes=("data",))
    comp = HierarchicalInt8EF(data_axes=("data",))
    assert dense.wire_bytes(grads) == dense_wire_bytes(grads)
    assert comp.wire_bytes(grads) == int8_wire_bytes(grads)
    ratio = dense.wire_bytes(grads) / comp.wire_bytes(grads)
    assert ratio >= 3.0  # f32 -> int8 is ~4x minus the per-leaf scale word


# ---------------------------------------------------------------------------
# strategy factory + mesh plumbing


def test_make_grad_reduce_factory():
    mesh2 = make_mesh((1, 1), ("data", "model"))
    mesh3 = make_mesh((1, 1, 1), ("pod", "data", "model"))
    assert make_grad_reduce("none", mesh2) is None
    assert make_grad_reduce("", mesh2) is None
    assert make_grad_reduce(None, mesh2) is None

    d = make_grad_reduce("dense", mesh3)
    assert isinstance(d, DenseReduce) and d.data_axes == ("pod", "data")

    c3 = make_grad_reduce("int8_ef", mesh3)
    assert c3.dcn_axis == "pod" and c3.ici_axes == ("data",)
    assert c3.dcn_size == 1 and c3.stateful
    c2 = make_grad_reduce("int8_ef", mesh2)  # no pod axis: all of "data" is DCN
    assert c2.dcn_axis == "data" and c2.ici_axes == ()

    with pytest.raises(ValueError, match="unknown grad_compression"):
        make_grad_reduce("fp8", mesh2)
    model_only = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="no data-like axis"):
        make_grad_reduce("dense", model_only)


def test_parse_mesh_arg_pod_axis():
    from repro.launch.mesh import parse_mesh_arg

    assert parse_mesh_arg("2x4") == (2, 4)
    assert parse_mesh_arg("2x2x1") == (2, 2, 1)
    for bad in ("2", "2x2x2x2", "0x1", "axb"):
        with pytest.raises(ValueError):
            parse_mesh_arg(bad)


def test_ef_state_layout():
    """EF residuals: one [dcn_size, *param] f32 block per leaf, sharded over
    the DCN axis on dim 0 so each pod rank owns exactly its own residual."""
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    gr = HierarchicalInt8EF(data_axes=("pod", "data"), dcn_axis="pod",
                            ici_axes=("data",), dcn_size=2)
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    ef = gr.init_state(params)
    assert ef["w"].shape == (2, 8, 4) and ef["w"].dtype == jnp.float32
    assert ef["b"].shape == (2, 4)
    sh = gr.state_shardings(params, mesh)
    assert sh["w"].spec == P("pod")
    assert gr.state_specs() == P("pod")


# ---------------------------------------------------------------------------
# dense shard_map step == legacy pjit step


def test_dense_shardmap_step_matches_legacy():
    """DenseReduce's explicit shard_map reduction must reproduce the legacy
    pjit step bit-for-bit (up to f32 roundoff): same grads, same Adam math,
    only the reduction is spelled out."""
    from repro.models.api import init_train_state, make_train_step

    cfg = tiny_dense(d_model=32, d_ff=64, vocab_size=128,
                     compute_dtype=jnp.float32)
    tc = fast_tc(steps=4, batch_size=4, seq_len=16)
    from repro.models.api import build_model

    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    batch = batch_for(cfg, B=4, S=16)

    p0, o0 = init_train_state(model, tc, jax.random.PRNGKey(0))
    legacy = jax.jit(make_train_step(model, tc))
    p_l, o_l = p0, o0
    for _ in range(3):
        p_l, o_l, m_l = legacy(p_l, o_l, batch)

    gr = make_grad_reduce("dense", mesh)
    sm = jax.jit(make_train_step(model, tc, grad_reduce=gr, mesh=mesh))
    p_s, o_s = p0, o0
    for _ in range(3):
        p_s, o_s, _, m_s = sm(p_s, o_s, None, batch)

    _assert_trees(p_l, p_s, atol=1e-5, err="params")
    np.testing.assert_allclose(float(m_l["loss"]), float(m_s["loss"]),
                               atol=1e-5)
    assert ef_psum_calls() == 0  # dense never touches the compressed path


def test_int8ef_shardmap_step_tracks_dense():
    """On a 1-rank DCN axis the compressed step's only deviation from dense is
    quantization noise, which EF keeps bounded -- a few steps must stay close,
    and the probe must record the traced compression."""
    from repro.models.api import (build_model, init_train_state,
                                  make_train_step, zero_train_state)

    cfg = tiny_dense(d_model=32, d_ff=64, vocab_size=128,
                     compute_dtype=jnp.float32)
    tc = fast_tc(steps=4, batch_size=4, seq_len=16)
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    batch = batch_for(cfg, B=4, S=16)
    p0, o0 = init_train_state(model, tc, jax.random.PRNGKey(0))

    dense = jax.jit(make_train_step(
        model, tc, grad_reduce=make_grad_reduce("dense", mesh), mesh=mesh))
    p_d, o_d = p0, o0
    for _ in range(4):
        p_d, o_d, _, _ = dense(p_d, o_d, None, batch)

    gr = make_grad_reduce("int8_ef", mesh)
    ef = gr.init_state(p0)
    comp = jax.jit(make_train_step(model, tc, grad_reduce=gr, mesh=mesh))
    p_c, o_c = p0, o0
    for _ in range(4):
        p_c, o_c, ef, _ = comp(p_c, o_c, ef, batch)

    assert ef_psum_calls() > 0  # the acceptance probe: traced, not configured
    _assert_trees(p_d, p_c, atol=1e-2, err="params")
    # the residual is alive (quantization really happened) and bounded
    ef_leaves = np.concatenate(
        [np.abs(np.asarray(l)).ravel() for l in jax.tree.leaves(ef)])
    assert ef_leaves.max() > 0.0


# ---------------------------------------------------------------------------
# EF-state lifecycle through the V-cycle (single device, mesh (1,1))


def _vcycle_pieces(compression):
    from repro.core.vcycle import VCycleRunner
    from repro.launch.train import make_batch_fn

    cfg, tc, ml = mp_arena()
    tc = dataclasses.replace(tc, grad_compression=compression)
    mesh = make_mesh((1, 1), ("data", "model"))
    bf = make_batch_fn(cfg, tc, shard=0)
    return cfg, tc, ml, mesh, bf, VCycleRunner


def test_vcycle_int8ef_runs_and_resets_ef_per_level(monkeypatch):
    """The full V-cycle under int8_ef: the EF tree is (re)initialized once per
    SEGMENT (level transitions change the shapes, so residuals must not leak
    across), its shapes track the current level, and the loss trajectory stays
    within quantization noise of the dense V-cycle."""
    cfg, tc, ml, mesh, bf, VCycleRunner = _vcycle_pieces("int8_ef")
    ref = VCycleRunner(cfg, ml, dataclasses.replace(tc, grad_compression="dense"),
                       bf, seed=0, mesh=mesh).run()

    runner = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh)
    inits = []
    orig = runner._init_ef

    def counting_init(level, params):
        inits.append(level)
        return orig(level, params)

    monkeypatch.setattr(runner, "_init_ef", counting_init)
    seen_shapes = {}

    def on_step(state, p, o, stopping, dt):
        leaf = jax.tree.leaves(state.ef)[0]
        seen_shapes.setdefault(state.seg_index, np.asarray(leaf).shape)

    out = runner.run(on_step=on_step)
    assert ef_psum_calls() > 0
    # one fresh EF init per segment: down(l0), up(l1), final(l0)
    assert inits == [p.level for p in runner.plan]
    # the residual block really tracks each segment's level shapes
    assert seen_shapes[0] != seen_shapes[1]  # l0 vs coalesced l1
    assert seen_shapes[0] == seen_shapes[2]  # final is back at l0
    assert len(out.history.loss) == len(ref.history.loss)
    np.testing.assert_allclose(out.history.loss, ref.history.loss, atol=5e-2)


def test_vcycle_ef_checkpoint_kill_and_resume(tmp_path):
    """EF-state lifecycle across save/kill/restore on one device: the residual
    tree rides the checkpoint, the restored run finishes identically to an
    uninterrupted one, and restoring WITHOUT the strategy fails loudly."""
    from repro.checkpoint import CheckpointManager
    from repro.launch.train import make_vcycle_save_cb, restore_vcycle_state

    cfg, tc, ml, mesh, bf, VCycleRunner = _vcycle_pieces("int8_ef")
    ref = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh).run()

    class Preempted(RuntimeError):
        pass

    runner = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh)
    cm = CheckpointManager(str(tmp_path))
    save_cb = make_vcycle_save_cb(cm, schedule=runner.plan)

    def killing_cb(state, p, o):
        save_cb(state, p, o, blocking=True)
        if state.global_step == 6:  # mid-upward-sweep: stash + EF both live
            raise Preempted

    with pytest.raises(Preempted):
        runner.run(ckpt_cb=killing_cb, ckpt_every=2)
    assert cm.latest()["meta"]["has_ef"] is True

    # restoring without the strategy must refuse, not silently drop residuals
    plain = VCycleRunner(cfg, ml,
                         dataclasses.replace(tc, grad_compression="none"),
                         bf, seed=0, mesh=mesh)
    with pytest.raises(ValueError, match="carries grad-reduction"):
        restore_vcycle_state(CheckpointManager(str(tmp_path)), plain, tc)

    resumed = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh)
    state, params, opt = restore_vcycle_state(
        CheckpointManager(str(tmp_path)), resumed, tc)
    assert (state.phase, state.global_step) == ("up", 6)
    assert state.ef is not None
    # residuals survived the roundtrip intact (nonzero = quantization actually
    # carried error into the save)
    ef_abs = np.concatenate(
        [np.abs(np.asarray(l)).ravel() for l in jax.tree.leaves(state.ef)])
    assert ef_abs.max() > 0.0
    out = resumed.run(state=state, params=params, opt_state=opt)
    assert out.history.step == ref.history.step
    _assert_trees(out.params, ref.params, atol=1e-4, err="resumed")


# ---------------------------------------------------------------------------
# KV streaming framing (satellite: bounded chunks over the coordination KV)


def _fake_kv(monkeypatch):
    import repro.distributed.multiprocess as mp

    store = {}
    monkeypatch.setattr(mp, "kv_put", lambda k, v: store.__setitem__(k, v))

    def fetch(k, timeout_ms=0):
        if k not in store:
            raise KeyError(k)
        return store[k]

    monkeypatch.setattr(mp, "kv_fetch", fetch)
    monkeypatch.setattr(mp, "kv_delete", lambda k: store.pop(k, None))
    return mp, store


def test_kv_stream_roundtrip_and_chunking(monkeypatch):
    mp, store = _fake_kv(monkeypatch)
    monkeypatch.setenv("REPRO_KV_CHUNK_BYTES", "4")
    payload = bytes(range(11))
    mp.kv_put_stream("s", payload)
    assert store["s/meta"] == b"n=3"  # ceil(11/4) parts
    # the jaxlib coordination service segfaults on 1-byte values: every
    # message the stream layer emits must be >= 2 bytes
    assert all(len(v) >= 2 for v in store.values()), {
        k: v for k, v in store.items() if len(v) < 2}
    assert mp.kv_fetch_stream("s") == payload
    mp.kv_delete_stream("s")
    assert not store  # parts AND meta reclaimed


def test_kv_stream_empty_and_single_part(monkeypatch):
    mp, store = _fake_kv(monkeypatch)
    mp.kv_put_stream("e", b"")
    assert store["e/meta"] == b"n=1"
    assert all(len(v) >= 2 for v in store.values())
    assert mp.kv_fetch_stream("e") == b""
    mp.kv_put_stream("one", b"abc")  # fits one default-size chunk
    assert store["one/meta"] == b"n=1"
    assert mp.kv_fetch_stream("one") == b"abc"
    mp.kv_delete_stream("e")
    mp.kv_delete_stream("one")
    mp.kv_delete_stream("never-put")  # missing meta: silent no-op
    assert not store


# ---------------------------------------------------------------------------
# sharding-aware restore geometry (satellite: fetch only addressed slices)


def test_chunk_intersects_geometry():
    from repro.checkpoint.store import chunk_intersects

    full = (8, 4)
    top = (slice(0, 4), slice(0, 4))
    bottom = (slice(4, 8), slice(0, 4))
    assert chunk_intersects([0, 0], [4, 4], [top], full)
    assert not chunk_intersects([4, 0], [4, 4], [top], full)
    assert chunk_intersects([2, 0], [4, 4], [top], full)  # straddles the cut
    assert chunk_intersects([4, 0], [4, 4], [top, bottom], full)
    # 0-d leaves carry empty index tuples and are always needed
    assert chunk_intersects([], [], [()], ())
    # slices with None bounds cover the whole dim
    assert chunk_intersects([4, 0], [4, 4], [(slice(None), slice(0, 2))], full)


class _StubSharding:
    def __init__(self, *idx):
        self._idx = idx

    def addressable_devices_indices_map(self, shape):
        return dict(enumerate(self._idx))


def test_needed_digests_prunes_unaddressed_chunks():
    from repro.checkpoint.store import needed_digests

    entries = {
        "w": {"shape": [8, 4], "dtype": "float32", "chunks": [
            {"digest": "top", "start": [0, 0], "shape": [4, 4]},
            {"digest": "bot", "start": [4, 0], "shape": [4, 4]}]},
        "b": {"shape": [4], "dtype": "float32", "chunks": [
            {"digest": "whole", "start": [0], "shape": [4]}]},
    }
    sh_top = _StubSharding((slice(0, 4), slice(0, 4)))
    # leaf with a sharding: only intersecting chunks; leaf without: everything
    assert needed_digests(entries, {"w": sh_top}) == {"top", "whole"}
    assert needed_digests(entries, {}) == {"top", "bot", "whole"}
    sh_full = _StubSharding((slice(0, 8), slice(0, 4)))
    assert needed_digests(entries, {"w": sh_full}) == {"top", "bot", "whole"}


def test_assemble_tree_skips_unneeded_chunks(tmp_path):
    from repro.checkpoint import ObjectStore
    from repro.checkpoint import store as store_lib

    pool = ObjectStore(str(tmp_path))
    top = np.arange(16, dtype=np.float32).reshape(4, 4)
    d_top = store_lib.leaf_digest(top)
    pool.put(d_top, top)  # the bottom chunk is NOT in any pool
    entries = {"w": {"shape": [8, 4], "dtype": "float32", "chunks": [
        {"digest": d_top, "start": [0, 0], "shape": [4, 4]},
        {"digest": "deadbeef", "start": [4, 0], "shape": [4, 4]}]}}
    # without pruning the missing chunk is fatal
    with pytest.raises(FileNotFoundError):
        store_lib.assemble_tree(entries, [pool])
    out = store_lib.assemble_tree(entries, [pool], needed={d_top})
    assert out["w"].shape == (8, 4) and out["w"].dtype == np.float32
    np.testing.assert_array_equal(out["w"][:4], top)
    # a fully-unneeded leaf still lands as a right-shaped placeholder
    out2 = store_lib.assemble_tree(entries, [pool], needed=set())
    assert out2["w"].shape == (8, 4) and out2["w"].dtype == np.float32


def test_np_dtype_resolves_ml_dtypes():
    from repro.checkpoint.store import np_dtype

    assert np_dtype("float32") == np.float32
    assert np_dtype(None) == np.float32
    assert np_dtype("bfloat16").itemsize == 2


# ---------------------------------------------------------------------------
# slow 2-process drills (the acceptance criteria)


@pytest.mark.slow
def test_two_process_int8ef_vcycle_tracks_dense(tmp_path):
    """The tentpole acceptance drill: a 2-process V-cycle over a real
    ("pod","data","model") mesh with --grad-compression int8_ef executes
    ef_int8_psum inside the shard_map'd compiled step (call probe) and its
    loss trajectory matches the dense run within quantization tolerance."""
    res = run_multiprocess("""
        import dataclasses, json, os
        import jax
        from repro.launch.mesh import make_mesh
        import numpy as np
        from helpers import mp_arena
        from repro.core.vcycle import VCycleRunner
        from repro.distributed import as_global_batch_fn
        from repro.distributed.compression import ef_psum_calls
        from repro.launch.train import make_batch_fn

        cfg, tc, ml = mp_arena()
        mesh = make_mesh((2, 1, 1), ("pod", "data", "model"))
        bf = as_global_batch_fn(make_batch_fn(cfg, tc, shard=0), mesh)

        dense = VCycleRunner(
            cfg, ml, dataclasses.replace(tc, grad_compression="dense"),
            bf, seed=0, mesh=mesh).run()
        assert ef_psum_calls() == 0  # dense never touches the probe
        comp = VCycleRunner(
            cfg, ml, dataclasses.replace(tc, grad_compression="int8_ef"),
            bf, seed=0, mesh=mesh).run()
        probe = ef_psum_calls()
        assert probe > 0, "compressed path never traced"
        dev = float(np.max(np.abs(np.asarray(dense.history.loss)
                                  - np.asarray(comp.history.loss))))
        print("MP_REDUCE", json.dumps({"probe": probe, "max_loss_dev": dev}),
              flush=True)
    """, n=2, env={"CK": str(tmp_path)})
    for rc, out in res:
        assert rc == 0, out[-3000:]
        line = [l for l in out.splitlines() if l.startswith("MP_REDUCE ")]
        assert line, out[-2000:]
        rep = json.loads(line[0].split(" ", 1)[1])
        assert rep["probe"] > 0
        # quantization noise only: a wrong shard/axis lands O(1) here
        assert rep["max_loss_dev"] < 5e-2, rep


@pytest.mark.slow
def test_two_process_ef_state_survives_kill_and_resume(tmp_path):
    """Kill-and-resume equivalence WITH live EF residuals: an int8_ef run
    killed mid-upward-sweep (SIGKILL semantics: the process dies right after
    a blocking coordinated save) resumes with the residual tree restored and
    finishes identically to the uninterrupted reference run."""
    ck_ref, ck = str(tmp_path / "ref"), str(tmp_path / "killed")
    res = run_multiprocess("""
        import dataclasses, os
        import jax
        from repro.launch.mesh import make_mesh
        from helpers import mp_arena
        from repro.checkpoint import CheckpointManager
        from repro.core.vcycle import VCycleRunner
        from repro.distributed import as_global_batch_fn
        from repro.launch.train import make_batch_fn, make_vcycle_save_cb

        class Preempted(RuntimeError):
            pass

        cfg, tc, ml = mp_arena()
        tc = dataclasses.replace(tc, grad_compression="int8_ef")
        mesh = make_mesh((2, 1, 1), ("pod", "data", "model"))
        bf = as_global_batch_fn(make_batch_fn(cfg, tc, shard=0), mesh)

        # uninterrupted reference, final params published for the outer test
        ref = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh).run()
        cm_ref = CheckpointManager(os.environ["CK_REF"])
        cm_ref.save(999, {"params": ref.params}, meta={"step": 999})

        # the killed run: blocking save at global step 6, then die
        runner = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh)
        cm = CheckpointManager(os.environ["CK"])
        save_cb = make_vcycle_save_cb(cm, schedule=runner.plan)

        def killing_cb(state, p, o):
            save_cb(state, p, o, blocking=True)
            if state.global_step == 6:  # mid-upward-sweep: stash + EF live
                raise Preempted

        try:
            runner.run(ckpt_cb=killing_cb, ckpt_every=2)
            raise AssertionError("kill never fired")
        except Preempted:
            print("MP_KILLED_OK", flush=True)
    """, n=2, env={"CK_REF": ck_ref, "CK": ck})
    for rc, out in res:
        assert rc == 0, out[-3000:]
        assert "MP_KILLED_OK" in out

    res = run_multiprocess("""
        import dataclasses, os
        import jax
        from repro.launch.mesh import make_mesh
        import numpy as np
        from helpers import mp_arena
        from repro.checkpoint import CheckpointManager
        from repro.core.vcycle import VCycleRunner
        from repro.distributed import as_global_batch_fn
        from repro.launch.train import make_batch_fn, restore_vcycle_state

        cfg, tc, ml = mp_arena()
        tc = dataclasses.replace(tc, grad_compression="int8_ef")
        mesh = make_mesh((2, 1, 1), ("pod", "data", "model"))
        bf = as_global_batch_fn(make_batch_fn(cfg, tc, shard=0), mesh)
        runner = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh)
        cm = CheckpointManager(os.environ["CK"])
        state, params, opt = restore_vcycle_state(cm, runner, tc)
        assert (state.phase, state.global_step) == ("up", 6)
        assert state.ef is not None
        leaf = jax.tree.leaves(state.ef)[0]
        assert leaf.shape[0] == 2  # one residual block per DCN (pod) rank
        assert leaf.sharding.spec == jax.sharding.PartitionSpec("pod")
        ef_abs = np.concatenate([np.abs(np.asarray(s.data)).ravel()
                                 for l in jax.tree.leaves(state.ef)
                                 for s in l.addressable_shards])
        assert ef_abs.max() > 0.0, "restored EF residuals are all-zero"
        out = runner.run(state=state, params=params, opt_state=opt)
        cm.save(999, {"params": out.params}, meta={"step": 999})
        print("MP_EF_RESUMED_OK", flush=True)
    """, n=2, env={"CK": ck})
    for rc, out in res:
        assert rc == 0, out[-3000:]
        assert "MP_EF_RESUMED_OK" in out

    from repro.checkpoint.manager import _read_leaves

    got = _read_leaves(os.path.join(ck, "step_00000999", "params"))
    want = _read_leaves(os.path.join(ck_ref, "step_00000999", "params"))
    assert got.keys() == want.keys()
    for k in got:
        np.testing.assert_allclose(np.asarray(got[k], np.float64),
                                   np.asarray(want[k], np.float64),
                                   atol=1e-4, err_msg=k)


@pytest.mark.slow
def test_two_process_localdir_restore_fetches_only_addressed_slices(tmp_path):
    """Satellite acceptance: a same-sharding --ckpt-local-dir restore must
    fetch ZERO sharded-leaf chunks from peers (each rank already holds the
    slices its shardings address); only rank-0-pooled replicated leaves cross
    the wire, and the skipped peer-half chunks show up in the stats."""
    res = run_multiprocess("""
        import json, os
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.experimental import multihost_utils
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.distributed import put_global_tree
        from repro.launch.mesh import make_mesh

        pid = jax.process_index()
        mesh = make_mesh((2, 1), ("data", "model"))
        sh_w = NamedSharding(mesh, P("data"))
        sh_b = NamedSharding(mesh, P())
        w = np.arange(32, dtype=np.float32).reshape(4, 8)
        b = np.arange(8, dtype=np.float32) + 100.0
        state = {"params": put_global_tree(
            {"w": jnp.asarray(w), "b": jnp.asarray(b)},
            {"w": sh_w, "b": sh_b})}
        cm = CheckpointManager(os.environ["CK"] + f"/local{pid}", local=True)
        cm.save(3, state, meta={"step": 3})

        like = {"params": {"w": jnp.zeros((4, 8)), "b": jnp.zeros(8)}}
        out, meta = cm.restore(like, shardings={"params": {"w": sh_w,
                                                           "b": sh_b}})
        assert meta["step"] == 3
        got_w = np.asarray(multihost_utils.process_allgather(
            out["params"]["w"], tiled=True))
        np.testing.assert_array_equal(got_w, w)
        np.testing.assert_array_equal(np.asarray(out["params"]["b"]), b)
        print("MP_STATS", json.dumps(cm.last_gather_stats), flush=True)
    """, n=2, env={"CK": str(tmp_path)})
    stats = []
    for rc, out in res:
        assert rc == 0, out[-3000:]
        line = [l for l in out.splitlines() if l.startswith("MP_STATS ")]
        assert line, out[-2000:]
        stats.append(json.loads(line[0].split(" ", 1)[1]))
    # manifest: 2 w-halves + 1 replicated b = 3 objects.  Each rank needs its
    # own w-half (held) + b; the peer's w-half is pruned, never fetched.
    for s in stats:
        assert s["manifest"] == 3, s
        assert s["skipped"] == 1, s  # the peer's half of w
    assert stats[0]["fetched"] == 0, stats  # rank 0 pooled b itself
    assert stats[1]["fetched"] == 1, stats  # rank 1 pulls only b
