"""Kill-and-resume equivalence: a 2-level V-cycle interrupted at an arbitrary
step (here: mid-upward-sweep, so the de-coalesce/interpolate transition is
replayed after restore) must produce final params and a FLOPs-indexed History
identical to the uninterrupted run; and each level's train step is compiled at
most once per run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import fast_tc, tiny_dense
from repro.checkpoint import CheckpointManager
from repro.config import MultiLevelConfig
from repro.core.vcycle import SegmentPlan, VCycleRunner, segments
from repro.data import MarkovLM, lm_batch
from repro.launch.mesh import make_mesh
from repro.launch.train import make_vcycle_save_cb, restore_vcycle_state


class Preempted(RuntimeError):
    pass


def arena():
    cfg = tiny_dense(d_model=32, d_ff=64, vocab_size=128,
                     compute_dtype=jnp.float32)
    tc = fast_tc(steps=12, batch_size=4, seq_len=16, log_every=2, peak_lr=3e-3)
    ml = MultiLevelConfig(n_levels=2, alpha=0.25, e_a_frac=0.25, e_small_frac=0.5)
    chain = MarkovLM(128)
    bf = lambda step: lm_batch(chain, 0, step, tc.batch_size, tc.seq_len)
    return cfg, ml, tc, bf


def test_segments_schedule():
    cfg, ml, tc, _ = arena()
    ml3 = MultiLevelConfig(n_levels=3, e_a_frac=0.25, e_small_frac=0.5)
    plan = segments(cfg, ml3, tc, final_steps=7)
    assert plan == [SegmentPlan("down", 0, 3), SegmentPlan("down", 1, 3),
                    SegmentPlan("up", 2, 6), SegmentPlan("up", 1, 6),
                    SegmentPlan("final", 0, 7)]


def test_kill_and_resume_equivalence(tmp_path):
    cfg, ml, tc, bf = arena()
    # schedule: down L0 for 3 steps (g 1..3), up L1 for 6 (g 4..9), final 12
    ref = VCycleRunner(cfg, ml, tc, bf, seed=0).run()

    # interrupted run: checkpoint every 2 global steps, die right after the
    # save at global step 6 -- the middle of the upward sweep
    cm = CheckpointManager(str(tmp_path))
    runner = VCycleRunner(cfg, ml, tc, bf, seed=0)
    save_cb = make_vcycle_save_cb(cm, schedule=runner.plan)

    def killing_cb(state, params, opt_state):
        save_cb(state, params, opt_state)
        if state.global_step == 6:
            raise Preempted

    with pytest.raises(Preempted):
        runner.run(ckpt_cb=killing_cb, ckpt_every=2)
    cm.wait()  # the real crash path relies on atomic publish instead

    # "new process": fresh runner, restore, run to completion
    runner2 = VCycleRunner(cfg, ml, tc, bf, seed=0)
    state, params, opt = restore_vcycle_state(cm, runner2, tc)
    assert (state.phase, state.level, state.global_step) == ("up", 1, 6)
    assert state.seg_step == 3 and state.seg_index == 1
    assert list(state.params_before) == [0]  # stash survives the crash
    out = runner2.run(state=state, params=params, opt_state=opt,
                      ckpt_cb=make_vcycle_save_cb(cm, schedule=runner2.plan),
                      ckpt_every=2)

    for a, b in zip(jax.tree.leaves(out.params), jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)
    assert out.history.step == ref.history.step
    assert out.history.level == ref.history.level
    np.testing.assert_allclose(out.history.flops, ref.history.flops, rtol=1e-12)
    np.testing.assert_allclose(out.history.loss, ref.history.loss, atol=1e-5)
    np.testing.assert_allclose(out.total_flops, ref.total_flops, rtol=1e-12)
    # resumed process compiled each visited level at most once
    assert runner2.n_compiles == 2


def test_restore_unsharded_save_onto_mesh(tmp_path):
    """Elastic re-shard, in-process flavor: a checkpoint written by an
    UNSHARDED run restores onto a mesh-carrying runner (1x1 fits the test
    process's single CPU device) -- params, opt and the mid-upward-sweep
    ``params_before_*`` stash all land as NamedSharding arrays, and the
    resumed sharded run matches the uninterrupted unsharded reference.
    (The multi-device 1x1 <-> 2x2 version lives in test_distributed.py.)"""
    from jax.sharding import NamedSharding

    cfg, ml, tc, bf = arena()
    ref = VCycleRunner(cfg, ml, tc, bf, seed=0).run()

    cm = CheckpointManager(str(tmp_path))
    runner = VCycleRunner(cfg, ml, tc, bf, seed=0)
    save_cb = make_vcycle_save_cb(cm, schedule=runner.plan)

    def killing_cb(state, params, opt_state):
        save_cb(state, params, opt_state)
        if state.global_step == 6:
            raise Preempted

    with pytest.raises(Preempted):
        runner.run(ckpt_cb=killing_cb, ckpt_every=2)
    cm.wait()

    mesh = make_mesh((1, 1), ("data", "model"))
    runner2 = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh)
    state, params, opt = restore_vcycle_state(cm, runner2, tc)
    for tree in (params, opt, state.params_before[0]):
        for leaf in jax.tree.leaves(tree):
            assert isinstance(leaf.sharding, NamedSharding)
    out = runner2.run(state=state, params=params, opt_state=opt)
    for a, b in zip(jax.tree.leaves(out.params), jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)
    assert out.history.step == ref.history.step


def test_resume_rejects_schedule_mismatch(tmp_path):
    """Restarting under different --steps/--levels must fail loudly, not
    silently train the wrong schedule from the restored (seg_index, seg_step)."""
    cfg, ml, tc, bf = arena()
    cm = CheckpointManager(str(tmp_path))
    runner = VCycleRunner(cfg, ml, tc, bf, seed=0)
    save_cb = make_vcycle_save_cb(cm, schedule=runner.plan)

    def killing_cb(state, params, opt_state):
        save_cb(state, params, opt_state)
        if state.global_step == 4:
            raise Preempted

    with pytest.raises(Preempted):
        runner.run(ckpt_cb=killing_cb, ckpt_every=2)
    cm.wait()

    tc2 = fast_tc(steps=30, batch_size=4, seq_len=16, log_every=2, peak_lr=3e-3)
    runner2 = VCycleRunner(cfg, ml, tc2, bf, seed=0)
    with pytest.raises(ValueError, match="schedule"):
        restore_vcycle_state(cm, runner2, tc2)


def test_no_checkpoint_on_early_stop_step(tmp_path):
    """A target-loss early exit is not persisted state, so the stopping step
    must never be checkpointed (a restart from it would train past the exit)."""
    cfg, ml, tc, bf = arena()
    cm = CheckpointManager(str(tmp_path))
    runner = VCycleRunner(cfg, ml, tc, bf, seed=0, target_loss=1e9)
    runner.run(ckpt_cb=make_vcycle_save_cb(cm, schedule=runner.plan),
               ckpt_every=1)
    cm.wait()
    # target trivially satisfied at the final segment's first log step (g=10);
    # every prior step checkpointed, the stopping step not
    assert runner.state.global_step == 10
    assert cm.latest()["step"] == 9


def test_per_level_step_compiled_once(monkeypatch):
    """The docstring promise: per-level compiled steps are built once and
    cached, even though levels below the top are visited twice."""
    import repro.core.vcycle as vc

    cfg, ml, tc, bf = arena()
    calls = []
    real = vc.make_train_step

    def counting(model, tc_):
        calls.append(model.cfg.d_model)
        return real(model, tc_)

    monkeypatch.setattr(vc, "make_train_step", counting)
    runner = VCycleRunner(cfg, ml, tc, bf, seed=0, final_steps=4)
    runner.run()
    assert runner.n_compiles == ml.n_levels
    assert sorted(calls) == sorted({cfg.d_model, cfg.d_model // 2})
