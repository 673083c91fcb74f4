"""Multi-process (multi-host) training tests.

The slow tests spawn N real local CPU processes against a localhost
coordinator (tests/helpers.py ``run_multiprocess``) -- the CI-drillable
stand-in for an N-host launch -- and pin the three advertised behaviors that
used to be dead or wrong:

* a 2-process ``(2,1)``-mesh V-cycle run consumes the same global data stream
  as a 1-process run and lands allclose final params (f32),
* coordinated checkpoints are process-count-elastic: save with 2 processes,
  resume with 1 (and vice versa), mid-upward-sweep with a live
  ``params_before`` stash,
* SIGTERM on any ONE process drains ALL processes through the same final save
  step and a clean exit 0 (cross-host preemption propagation).
"""
import json
import os
import re
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from helpers import free_port, mp_arena, run_multiprocess
from repro.launch.mesh import make_mesh

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# fast single-process guarantees


def test_single_process_helpers_degrade_to_noops():
    from repro.distributed import any_process_flag, as_global_batch_fn, barrier

    barrier("noop")  # must not require jax.distributed
    assert any_process_flag(True) is True
    assert any_process_flag(False) is False
    bf = lambda step: {"x": np.zeros((4, 2))}
    mesh = make_mesh((1, 1), ("data", "model"))
    assert as_global_batch_fn(bf, mesh) is bf  # identity, not a wrapper
    assert as_global_batch_fn(bf, None) is bf


def test_preemption_guard_should_stop_single_process():
    from repro.launch.train import PreemptionGuard

    g = PreemptionGuard()
    assert g.should_stop() is False
    g.triggered = True
    assert g.should_stop() is True


class _NotAddressable:
    """Stub for an array sharded across processes (can't build a real one in
    a single-process test)."""

    is_fully_addressable = False
    shape = (2,)


def test_save_tree_raises_on_non_addressable(tmp_path):
    """The old path silently jax.device_get'ed every leaf ("one process owns
    all shards"); feeding it a cross-process-sharded leaf must raise loudly
    instead of gathering garbage."""
    from repro.checkpoint import save_tree

    with pytest.raises(ValueError, match="not fully addressable"):
        save_tree(str(tmp_path / "t"), {"w": _NotAddressable()})


def test_manager_save_raises_on_non_addressable(tmp_path):
    from repro.checkpoint import CheckpointManager

    cm = CheckpointManager(str(tmp_path))
    with pytest.raises(ValueError, match="not fully addressable"):
        cm.save(1, {"params": {"w": _NotAddressable()}})
    assert cm.latest() is None  # nothing was published


def test_fused_drain_flag_single_mesh_mechanics():
    """The fused drain path end to end on a 1-device mesh: the flag array is
    authored per process, the in-step reduce replicates it, and the guard
    reads the fused scalar instead of all-gathering."""
    import jax.numpy as jnp

    from repro.distributed import FusedDrainFlag
    from repro.launch.train import PreemptionGuard

    mesh = make_mesh((1, 1), ("data", "model"))
    g = PreemptionGuard()
    drain = g.attach(FusedDrainFlag(mesh, guard=g))
    assert g.should_stop() is False  # nothing observed yet

    step = jax.jit(lambda flag: FusedDrainFlag.reduce(flag))
    drain.observe(step(drain.device_flag()))
    assert drain.last() is False and g.should_stop() is False
    g.triggered = True
    drain.observe(step(drain.device_flag()))
    assert drain.last() is True and g.should_stop() is True
    # un-attached guards keep the explicit allgather fallback
    g2 = PreemptionGuard()
    g2.triggered = True
    assert g2.should_stop() is True


def test_fused_drain_guard_local_flag_before_first_step():
    """Single-process safety net: a SIGTERM caught before the first fused
    step is observed must still stop at the next poll."""
    from repro.distributed import FusedDrainFlag
    from repro.launch.train import PreemptionGuard

    mesh = make_mesh((1, 1), ("data", "model"))
    g = PreemptionGuard()
    g.attach(FusedDrainFlag(mesh, guard=g))
    g.triggered = True
    assert g.should_stop() is True


def test_make_cli_mesh_rejects_indivisible_process_count():
    from repro.launch.mesh import make_cli_mesh

    with pytest.raises(ValueError, match="not divisible"):
        make_cli_mesh("3x1", num_processes=2)


# ---------------------------------------------------------------------------
# real 2-process drills


def _final_params(ckdir: str, step_dir: str = None):
    """Reassembled logical final params from a checkpoint dir, whatever
    layout (whole-leaf or coordinated shard chunks) wrote it."""
    from repro.checkpoint.manager import _read_leaves

    if step_dir is None:
        m = json.load(open(os.path.join(ckdir, "manifest.json")))
        step_dir = m["dir"]
    return _read_leaves(os.path.join(ckdir, step_dir, "params"))


def _flat_params(tree):
    from repro.checkpoint.manager import _flatten

    return _flatten(jax.device_get(tree))


def _assert_allclose_trees(a, b, atol):
    assert a.keys() == b.keys(), (sorted(a)[:3], sorted(b)[:3])
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k], np.float64),
                                   np.asarray(b[k], np.float64),
                                   atol=atol, err_msg=k)


@pytest.mark.slow
def test_two_process_vcycle_matches_single_process(tmp_path):
    """The acceptance drill: a 2-process (2,1)-mesh V-cycle through the real
    driver (train_vcycle_ckpt + coordinated checkpointing) reproduces the
    single-process run's final params.  f32; the 1e-2 atol is a gross-error
    guard -- per-step drift is pure data-parallel reduction roundoff (~1e-6
    measured) that Adam amplifies, while a wrong shard/slice lands O(1e-1)."""
    res = run_multiprocess("""
        import os
        import jax
        from repro.launch.mesh import make_mesh
        from helpers import mp_arena
        from repro.checkpoint import CheckpointManager
        from repro.distributed import mesh_ctx
        from repro.launch.train import train_vcycle_ckpt

        cfg, tc, ml = mp_arena()
        mesh = make_mesh((2, 1), ("data", "model"))
        cm = CheckpointManager(os.environ["CK"])
        with mesh_ctx(mesh):
            out = train_vcycle_ckpt(cfg, ml, tc, ckpt=cm, ckpt_every=4,
                                    mesh=mesh,
                                    verbose=jax.process_index() == 0)
        print("MP_VCYCLE_OK", flush=True)
    """, n=2, env={"CK": str(tmp_path)})
    for rc, out in res:
        assert rc == 0, out[-3000:]
        assert "MP_VCYCLE_OK" in out
    # single-process reference, same global data stream by construction
    from repro.core.vcycle import VCycleRunner
    from repro.launch.train import make_batch_fn

    cfg, tc, ml = mp_arena()
    ref = VCycleRunner(cfg, ml, tc, make_batch_fn(cfg, tc, shard=0),
                       seed=tc.seed).run()
    m = json.load(open(os.path.join(str(tmp_path), "manifest.json")))
    assert m["meta"].get("phase") == "done"
    _assert_allclose_trees(_final_params(str(tmp_path)),
                           _flat_params(ref.params), atol=1e-2)
    np.testing.assert_allclose(m["meta"]["history"]["loss"],
                               ref.history.loss, atol=1e-2)


@pytest.mark.slow
def test_checkpoint_crosses_process_counts_both_ways(tmp_path):
    """Elastic restore across PROCESS COUNTS, mid-upward-sweep (live
    ``params_before`` stash): a checkpoint coordinated-saved by 2 processes
    resumes under 1 process, and a 1-process save resumes under 2 processes
    -- both runs land allclose to the uninterrupted single-process
    reference."""
    from repro.checkpoint import CheckpointManager
    from repro.core.vcycle import VCycleRunner
    from repro.launch.train import (make_batch_fn, make_vcycle_save_cb,
                                    restore_vcycle_state)

    cfg, tc, ml = mp_arena()
    bf = make_batch_fn(cfg, tc, shard=0)
    ref = VCycleRunner(cfg, ml, tc, bf, seed=0).run()

    # --- 2-process save, killed right after the global_step-6 checkpoint ----
    ck2 = str(tmp_path / "two_to_one")
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
        cm = CheckpointManager(os.environ["CK"])
        save_cb = make_vcycle_save_cb(cm, schedule=runner.plan)

        def killing_cb(state, params, opt_state):
            save_cb(state, params, opt_state)
            if state.global_step == 6:  # mid-upward-sweep: stash is live
                raise Preempted

        try:
            runner.run(ckpt_cb=killing_cb, ckpt_every=2)
            raise AssertionError("kill never fired")
        except Preempted:
            print("MP_KILLED_OK", flush=True)
    """, n=2, env={"CK": ck2})
    for rc, out in res:
        assert rc == 0, out[-3000:]
        assert "MP_KILLED_OK" in out

    # ...resumed by ONE process, no mesh at all
    runner1 = VCycleRunner(cfg, ml, tc, bf, seed=0)
    state, params, opt = restore_vcycle_state(CheckpointManager(ck2), runner1, tc)
    assert (state.phase, state.level, state.global_step) == ("up", 1, 6)
    assert list(state.params_before) == [0]
    out1 = runner1.run(state=state, params=params, opt_state=opt)
    assert out1.history.step == ref.history.step
    _assert_allclose_trees(_flat_params(out1.params), _flat_params(ref.params),
                           atol=1e-2)

    # --- 1-process save killed at the same point, resumed by 2 processes ----
    ck1 = str(tmp_path / "one_to_two")

    class Preempted(RuntimeError):
        pass

    runner_s = VCycleRunner(cfg, ml, tc, bf, seed=0)
    cm_s = CheckpointManager(ck1)
    save_cb = make_vcycle_save_cb(cm_s, schedule=runner_s.plan)

    def killing_cb(state, p, o):
        save_cb(state, p, o, blocking=True)
        if state.global_step == 6:
            raise Preempted

    with pytest.raises(Preempted):
        runner_s.run(ckpt_cb=killing_cb, ckpt_every=2)

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
        cm = CheckpointManager(os.environ["CK"])
        state, params, opt = restore_vcycle_state(cm, runner, tc)
        assert (state.phase, state.level, state.global_step) == ("up", 1, 6)
        # the restored stash really spans the 2-process mesh
        leaf = jax.tree.leaves(state.params_before[0])[0]
        assert leaf.sharding.mesh.devices.size == 2
        out = runner.run(state=state, params=params, opt_state=opt)
        cm.save(999, {"params": out.params}, meta={"step": 999})
        print("MP_RESUMED_OK", flush=True)
    """, n=2, env={"CK": ck1})
    for rc, out in res:
        assert rc == 0, out[-3000:]
        assert "MP_RESUMED_OK" in out
    _assert_allclose_trees(_final_params(ck1, "step_00000999"),
                           _flat_params(ref.params), atol=1e-2)


@pytest.mark.slow
def test_v2_coordinated_save_writes_meta_for_scan_fallback(tmp_path):
    """Regression: the v2 (``dedup=False``) coordinated save must write
    ``meta.json`` into the step dir -- the torn-manifest ``_scan_fallback``
    recovers metadata from it, and losing it silently drops the VCycleState
    addressing on recovery."""
    res = run_multiprocess("""
        import os
        import jax, jax.numpy as jnp
        from repro.checkpoint import CheckpointManager

        cm = CheckpointManager(os.environ["CK"], dedup=False)
        cm.save(7, {"params": {"w": jnp.arange(4.0)}},
                meta={"step": 7, "phase": "up"})
        print("MP_V2_SAVED", flush=True)
    """, n=2, env={"CK": str(tmp_path)})
    for rc, out in res:
        assert rc == 0, out[-3000:]
        assert "MP_V2_SAVED" in out
    assert os.path.exists(os.path.join(str(tmp_path), "step_00000007",
                                       "meta.json"))
    # torn manifest: points at a dir that no longer exists -> scan fallback
    with open(os.path.join(str(tmp_path), "manifest.json"), "w") as f:
        json.dump({"dir": "step_00000099", "step": 99, "meta": {}}, f)
    from repro.checkpoint import CheckpointManager

    m = CheckpointManager(str(tmp_path)).latest()
    assert m["step"] == 7 and m["meta"]["phase"] == "up"


@pytest.mark.slow
def test_fused_drain_no_dedicated_allgather(tmp_path):
    """ROADMAP open item closed: the per-step drain poll must run ZERO
    dedicated ``process_allgather`` calls (the OR is fused into the compiled
    step), while a flag raised on ONE process still drains BOTH at the same
    agreed global step."""
    res = run_multiprocess("""
        import jax
        from repro.launch.mesh import make_mesh
        from jax.experimental import multihost_utils as mh
        calls = {"n": 0}
        orig = mh.process_allgather
        def counting(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)
        mh.process_allgather = counting

        from helpers import mp_arena
        from repro.core.vcycle import VCycleRunner
        from repro.distributed import FusedDrainFlag, as_global_batch_fn
        from repro.launch.train import PreemptionGuard, make_batch_fn

        cfg, tc, ml = mp_arena()
        mesh = make_mesh((2, 1), ("data", "model"))
        bf = as_global_batch_fn(make_batch_fn(cfg, tc, shard=0), mesh)
        guard = PreemptionGuard()
        drain = guard.attach(FusedDrainFlag(mesh, guard=guard))
        runner = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh,
                              drain_flag=drain)

        def on_step(st, p, o, stopping, dt):
            if jax.process_index() == 1 and st.global_step == 5:
                guard.triggered = True  # the notice lands on ONE process only
            if guard.should_stop() and not stopping:
                print("DRAIN_AT", st.global_step, "ALLGATHERS", calls["n"],
                      flush=True)
                raise SystemExit(0)

        runner.run(on_step=on_step)
        raise AssertionError("drain never fired")
    """, n=2)
    steps = []
    for rc, out in res:
        assert rc == 0, out[-3000:]
        m = re.search(r"DRAIN_AT (\d+) ALLGATHERS (\d+)", out)
        assert m is not None, out[-2000:]
        steps.append(m.group(1))
        assert m.group(2) == "0", out[-2000:]
    assert steps[0] == steps[1]  # one agreed final step on both processes


@pytest.mark.slow
def test_sigterm_on_one_process_drains_all(tmp_path):
    """Cross-host preemption through the real CLI: SIGTERM delivered to
    process 1 ONLY must drain BOTH processes through the same final-save step
    and exit 0, and the checkpoint must resume under a single process."""
    port = free_port()
    common = [sys.executable, "-m", "repro.launch.train", "--arch",
              "tinyllama-1.1b", "--smoke", "--vcycle", "--levels", "2",
              "--steps", "40", "--batch", "4", "--seq", "16", "--f32",
              "--ckpt-dir", str(tmp_path), "--ckpt-every", "1000"]
    mp = ["--mesh", "2x1", "--coordinator", f"127.0.0.1:{port}",
          "--num-processes", "2"]
    env = dict(os.environ, PYTHONPATH="src")
    logs = [os.path.join(str(tmp_path), f"rank{i}.log") for i in (0, 1)]
    procs = []
    for i in (0, 1):
        with open(logs[i], "w") as lf:
            procs.append(subprocess.Popen(
                common + mp + ["--process-id", str(i)], env=env, cwd=ROOT,
                stdout=lf, stderr=subprocess.STDOUT))
    try:
        deadline = time.time() + 300
        stepping = False
        while time.time() < deadline and not stepping:
            if any(p.poll() is not None for p in procs):
                break
            stepping = "coalescing" in open(logs[0]).read()
            time.sleep(0.1)
        assert stepping, (open(logs[0]).read()[-2000:],
                          open(logs[1]).read()[-2000:])
        procs[1].send_signal(signal.SIGTERM)  # ONE process gets the notice
        for p in procs:
            assert p.wait(timeout=300) == 0, "drain exit was not clean"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    outs = [open(l).read() for l in logs]
    steps = [re.search(r"blocking V-cycle checkpoint at global_step (\d+)", o)
             for o in outs]
    assert all(s is not None for s in steps), (outs[0][-1500:], outs[1][-1500:])
    # ...at the SAME agreed step on both processes
    assert steps[0].group(1) == steps[1].group(1)
    assert "caught signal" in outs[1] and "caught signal" not in outs[0]
    assert os.path.exists(os.path.join(str(tmp_path), "manifest.json"))
    # the 2-process drain checkpoint resumes under ONE process
    r = subprocess.run(common, capture_output=True, text=True, env=env,
                       cwd=ROOT, timeout=480)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "resumed at phase=" in r.stdout, r.stdout[-1500:]
