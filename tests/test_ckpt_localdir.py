"""Per-host LOCAL checkpoint dirs (clusters without a shared filesystem).

The acceptance drills: a V-cycle killed mid-upward-sweep (live
``params_before_0`` stash) whose checkpoints were coordinated-saved by 2
processes into two DISJOINT ``local=True`` dirs resumes on 1 process (reading
the peer dir as a recovered pool), and a 1-process local save resumes on 2
processes (the missing objects travel over the coordination-service KV) --
both land allclose to the uninterrupted single-process reference, and the
local-dir restore is BIT-identical to the shared-dir restore of the same run.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import mp_arena, run_multiprocess
from repro.checkpoint import CheckpointManager, ObjectStore
from repro.checkpoint.manager import _flatten, _read_leaves
from repro.core.vcycle import VCycleRunner
from repro.launch.train import (make_batch_fn, make_vcycle_save_cb,
                                restore_vcycle_state)


def _flat(tree):
    return _flatten(jax.device_get(tree))


def _assert_trees(a, b, atol, err=""):
    a, b = _flat(a), _flat(b)
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k], np.float64),
                                   np.asarray(b[k], np.float64),
                                   atol=atol, err_msg=f"{err}:{k}")


# ---------------------------------------------------------------------------
# fast single-process guarantees


def test_local_manager_single_process_is_plain_v3(tmp_path):
    cm = CheckpointManager(str(tmp_path), local=True)
    assert cm.dedup  # local mode is v3-only
    st = {"params": {"w": jnp.arange(6.0)}}
    cm.save(3, st, meta={"step": 3})
    out, meta = cm.restore(jax.tree.map(jnp.zeros_like, st))
    assert meta["step"] == 3
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.arange(6.0))


def test_peer_dirs_resolve_missing_objects(tmp_path):
    """An object held only by a peer's recovered dir is found at restore."""
    own, peer = str(tmp_path / "own"), str(tmp_path / "peer")
    cm_writer = CheckpointManager(peer, local=True)
    st = {"params": {"w": jnp.arange(8.0)}}
    cm_writer.save(1, st, meta={"step": 1})
    # move the published manifest (but not the pool) to the "own" dir,
    # simulating the process-0 dir of a host whose chunks lived elsewhere
    os.makedirs(own)
    os.rename(os.path.join(peer, "manifest.json"),
              os.path.join(own, "manifest.json"))
    os.rename(os.path.join(peer, "step_00000001"),
              os.path.join(own, "step_00000001"))
    cm = CheckpointManager(own, peer_dirs=[peer])
    out, meta = cm.restore(jax.tree.map(jnp.zeros_like, st))
    assert meta["step"] == 1
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.arange(8.0))
    # without the peer pool the same restore must fail loudly
    with pytest.raises(FileNotFoundError, match="not found in any pool"):
        CheckpointManager(own).restore(jax.tree.map(jnp.zeros_like, st))


# ---------------------------------------------------------------------------
# the acceptance drills (2 real processes)


@pytest.mark.slow
def test_two_process_local_dirs_resume_on_one_process(tmp_path):
    """2-process save into two disjoint --ckpt-local-dir style dirs, killed
    right after the mid-upward-sweep save at global step 6; a SINGLE process
    resumes from local0 + the recovered local1 pool.  The restored trees are
    bit-identical to the shared-dir restore of the very same run, and the
    finished resume lands allclose to the uninterrupted reference."""
    res = run_multiprocess("""
        import os
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
        mesh = make_mesh((2, 1), ("data", "model"))
        bf = as_global_batch_fn(make_batch_fn(cfg, tc, shard=0), mesh)
        runner = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh)
        # BOTH paths from the same run: a shared-dir manager (the reference
        # layout) and a per-process local-dir manager (the layout under test);
        # same construction order on every rank keeps KV scopes aligned
        cm_shared = CheckpointManager(os.environ["CK_SHARED"])
        cm_local = CheckpointManager(
            os.environ["CK_BASE"] + f"/local{jax.process_index()}", local=True)
        cb_shared = make_vcycle_save_cb(cm_shared, schedule=runner.plan)
        cb_local = make_vcycle_save_cb(cm_local, schedule=runner.plan)

        def killing_cb(state, params, opt_state):
            cb_shared(state, params, opt_state)
            cb_local(state, params, opt_state)
            if state.global_step == 6:  # mid-upward-sweep: stash is live
                raise Preempted

        try:
            runner.run(ckpt_cb=killing_cb, ckpt_every=2)
            raise AssertionError("kill never fired")
        except Preempted:
            print("MP_KILLED_OK", flush=True)
    """, n=2, env={"CK_SHARED": str(tmp_path / "shared"),
                   "CK_BASE": str(tmp_path)})
    for rc, out in res:
        assert rc == 0, out[-3000:]
        assert "MP_KILLED_OK" in out

    cfg, tc, ml = mp_arena()
    bf = make_batch_fn(cfg, tc, shard=0)
    ref = VCycleRunner(cfg, ml, tc, bf, seed=0).run()

    # single-process restore: local0 is the primary, local1 a recovered pool
    cm_local = CheckpointManager(str(tmp_path / "local0"),
                                 peer_dirs=[str(tmp_path / "local1")])
    runner_l = VCycleRunner(cfg, ml, tc, bf, seed=0)
    state_l, params_l, opt_l = restore_vcycle_state(cm_local, runner_l, tc)
    assert (state_l.phase, state_l.level, state_l.global_step) == ("up", 1, 6)
    assert list(state_l.params_before) == [0]

    # the local-dir restore is BIT-identical to the shared-dir restore
    cm_shared = CheckpointManager(str(tmp_path / "shared"))
    runner_s = VCycleRunner(cfg, ml, tc, bf, seed=0)
    state_s, params_s, opt_s = restore_vcycle_state(cm_shared, runner_s, tc)
    _assert_trees(params_l, params_s, atol=0, err="params")
    _assert_trees(opt_l, opt_s, atol=0, err="opt")
    _assert_trees(state_l.params_before[0], state_s.params_before[0],
                  atol=0, err="stash")

    # and the finished resume matches the uninterrupted reference
    out_l = runner_l.run(state=state_l, params=params_l, opt_state=opt_l)
    assert out_l.history.step == ref.history.step
    _assert_trees(out_l.params, ref.params, atol=1e-2, err="final")


@pytest.mark.slow
def test_latest_survives_rank0_dir_loss(tmp_path):
    """Losing rank 0's local dir -- the exact failure per-host dirs must
    tolerate -- must NOT make the job silently forget the checkpoint: the
    coordinated ``latest()`` picks the newest manifest across EVERY rank's
    dir, and the surviving rank serves all objects over the KV gather."""
    survivor = str(tmp_path / "survivor")
    # written by ONE process => the survivor's pool holds every object
    cm = CheckpointManager(survivor, local=True)
    cm.save(5, {"params": {"w": jnp.arange(8.0)}}, meta={"step": 5})

    res = run_multiprocess("""
        import os
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.checkpoint import CheckpointManager

        # rank 0 restarts on a FRESH (lost) dir; rank 1 has the survivor
        my_dir = (os.environ["FRESH"] if jax.process_index() == 0
                  else os.environ["SURVIVOR"])
        cm = CheckpointManager(my_dir, local=True)
        out, meta = cm.restore({"params": {"w": jnp.zeros(8)}})
        assert meta["step"] == 5, meta
        np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                      np.arange(8.0))
        print("MP_SURVIVED_OK", flush=True)
    """, n=2, env={"FRESH": str(tmp_path / "fresh"), "SURVIVOR": survivor})
    for rc, out in res:
        assert rc == 0, out[-3000:]
        assert "MP_SURVIVED_OK" in out


@pytest.mark.slow
def test_one_process_local_save_resumes_on_two_processes(tmp_path):
    """The reverse direction: a 1-process local-dir save killed at the same
    mid-upward-sweep point resumes under 2 processes -- rank 1 starts with an
    EMPTY local dir and gathers every object over the coordination KV."""
    cfg, tc, ml = mp_arena()
    bf = make_batch_fn(cfg, tc, shard=0)
    ref = VCycleRunner(cfg, ml, tc, bf, seed=0).run()

    class Preempted(RuntimeError):
        pass

    save_dir = str(tmp_path / "local0")
    runner = VCycleRunner(cfg, ml, tc, bf, seed=0)
    cm = CheckpointManager(save_dir, local=True)
    save_cb = make_vcycle_save_cb(cm, schedule=runner.plan)

    def killing_cb(state, p, o):
        save_cb(state, p, o, blocking=True)
        if state.global_step == 6:
            raise Preempted

    with pytest.raises(Preempted):
        runner.run(ckpt_cb=killing_cb, ckpt_every=2)

    res = run_multiprocess("""
        import os
        import jax
        from repro.launch.mesh import make_mesh
        from helpers import mp_arena
        from repro.checkpoint import CheckpointManager
        from repro.core.vcycle import VCycleRunner
        from repro.distributed import as_global_batch_fn
        from repro.launch.train import make_batch_fn, restore_vcycle_state

        cfg, tc, ml = mp_arena()
        mesh = make_mesh((2, 1), ("data", "model"))
        bf = as_global_batch_fn(make_batch_fn(cfg, tc, shard=0), mesh)
        runner = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh)
        # rank 0 owns the dir that saved; rank 1's dir is fresh and empty
        my_dir = (os.environ["CK0"] if jax.process_index() == 0
                  else os.environ["CK1"])
        cm = CheckpointManager(my_dir, local=True)
        state, params, opt = restore_vcycle_state(cm, runner, tc)
        assert (state.phase, state.level, state.global_step) == ("up", 1, 6)
        # the restored stash really spans the 2-process mesh
        leaf = jax.tree.leaves(state.params_before[0])[0]
        assert leaf.sharding.mesh.devices.size == 2
        out = runner.run(state=state, params=params, opt_state=opt)
        cm.save(999, {"params": out.params}, meta={"step": 999})
        print("MP_RESUMED_OK", flush=True)
    """, n=2, env={"CK0": save_dir, "CK1": str(tmp_path / "local1")})
    for rc, out in res:
        assert rc == 0, out[-3000:]
        assert "MP_RESUMED_OK" in out

    # the final coordinated local save: every rank published the manifest
    # into its own dir; chunks resolve across the two pools
    for d in (save_dir, str(tmp_path / "local1")):
        m = json.load(open(os.path.join(d, "manifest.json")))
        assert m["step"] == 999
    flat = _read_leaves(os.path.join(save_dir, "step_00000999", "params"),
                        pools=[ObjectStore(save_dir),
                               ObjectStore(str(tmp_path / "local1"))])
    ref_flat = _flat(ref.params)
    assert flat.keys() == ref_flat.keys()
    for k in flat:
        np.testing.assert_allclose(np.asarray(flat[k], np.float64),
                                   np.asarray(ref_flat[k], np.float64),
                                   atol=1e-2, err_msg=k)
