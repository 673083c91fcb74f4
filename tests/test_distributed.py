"""Sharding rules + a reduced dry-run in a subprocess (8 placeholder devices)
-- proving the mesh/sharding machinery without pinning 512 devices into the
test process."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import RULES, logical_spec
from repro.launch.mesh import make_mesh


class FakeMesh:
    """Duck-typed mesh for pure spec-rule tests (axis_names + shape only)."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


SINGLE = FakeMesh({"data": 16, "model": 16})
MULTI = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_fsdp_tp_placement():
    # FFN weight: embed -> fsdp axes, mlp -> model
    assert logical_spec((7168, 2048), ("embed", "mlp"), SINGLE) == P("data", "model")
    assert logical_spec((7168, 2048), ("embed", "mlp"), MULTI) == P(("pod", "data"), "model")
    # expert weights: EP on model, embed FSDP'd
    assert logical_spec((256, 7168, 2048), ("experts", "embed", "moe_mlp"), SINGLE) == \
        P("model", "data", None)


def test_nondivisible_axes_replicate():
    # 40 heads on 16-way model axis -> replicated (documented in qwen3-14b)
    assert logical_spec((5120, 40, 128), ("embed", "heads", "head_dim"), SINGLE) == \
        P("data", None, None)
    # batch=1 long-context decode cannot shard batch
    assert logical_spec((1, 1), ("batch", "seq"), SINGLE) == P(None, None)


def test_no_mesh_axis_used_twice():
    spec = logical_spec((64, 64), ("vocab", "heads"), SINGLE)
    flat = [s for s in spec if s is not None]
    assert len(flat) == len(set(flat)) == 1  # "model" assigned once only


def test_cache_seq_sharding():
    assert logical_spec((128, 32768, 8, 128),
                        ("batch", "cache_seq", "cache_kv_heads", "head_dim"), SINGLE) == \
        P("data", "model", None, None)


def test_batch_axis_sharding_divisibility():
    """The launcher's batch shardings: leading dim over the data axes when
    divisible, replicated otherwise (ragged smoke batches must still lower)."""
    mesh = FakeMesh({"data": 2, "model": 2})
    assert logical_spec((8, 16), ("batch", "seq"), mesh) == P("data", None)
    assert logical_spec((3, 16), ("batch", "seq"), mesh) == P(None, None)


def test_batch_shardings_tree():
    from repro.distributed import batch_shardings

    mesh = make_mesh((1, 1), ("data", "model"))
    like = {"tokens": jax.ShapeDtypeStruct((4, 16), np.int32),
            "labels": jax.ShapeDtypeStruct((4, 16), np.int32)}
    sh = batch_shardings(like, mesh)
    assert set(sh) == {"tokens", "labels"}
    assert sh["tokens"].spec == P("data", None)


def test_data_shard_index_single_process():
    """One process owns every shard-0 batch regardless of mesh shape, so
    cross-mesh resume equivalence is well-posed on this container."""
    from repro.distributed import data_shard_index

    assert data_shard_index() == jax.process_index() == 0
    mesh = make_mesh((1, 1), ("data", "model"))
    assert data_shard_index(mesh) == 0


@pytest.mark.slow
def test_cross_mesh_vcycle_restore_equivalence(tmp_path):
    """Elastic mid-V-cycle re-shard: a run killed mid-upward-sweep under mesh
    A (so a ``params_before_*`` stash is live) restores under mesh B -- in
    BOTH directions, 1x1 <-> 2x2.  Pins three things: (1) the restored
    params/opt/stash values are EXACTLY the checkpoint's regardless of target
    mesh, (2) the resumed sharded run replays the exact segment schedule of
    an uninterrupted unsharded reference, (3) final params stay allclose to
    that reference.  (3) is tolerance-bound: a single cross-mesh step differs
    only by reduction-order roundoff (~3e-8 measured), but Adam's
    sign-normalized updates amplify it over the remaining steps, so the drift
    scales with lr -- the test trains at peak_lr=3e-4 and the 1e-2 atol is a
    gross-error guard (a wrong leaf/stash or a broken sharded projection --
    e.g. the concatenate-with-self GSPMD miscompile this test originally
    caught in ``_stack_decoalesce`` -- lands at the O(1e-1)+ scale); bitwise
    restore correctness is pinned by (1), not (3).  Runs in a subprocess with
    4 forced host devices (the test process must keep its single real CPU
    device)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from helpers import fast_tc, tiny_dense
        from repro.checkpoint import CheckpointManager
        from repro.config import MultiLevelConfig
        from repro.core.vcycle import VCycleRunner
        from repro.data import MarkovLM, lm_batch
        from repro.launch.mesh import make_mesh
        from repro.launch.train import make_vcycle_save_cb, restore_vcycle_state

        class Preempted(RuntimeError):
            pass

        cfg = tiny_dense(d_model=32, d_ff=64, vocab_size=128,
                         compute_dtype=jnp.float32)
        tc = fast_tc(steps=12, batch_size=4, seq_len=16, log_every=2,
                     peak_lr=3e-4)
        ml = MultiLevelConfig(n_levels=2, alpha=0.25, e_a_frac=0.25,
                              e_small_frac=0.5)
        chain = MarkovLM(128)
        bf = lambda s: lm_batch(chain, 0, s, tc.batch_size, tc.seq_len)
        ref = VCycleRunner(cfg, ml, tc, bf, seed=0).run()

        def exact_equal(ta, tb, name):
            for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
                d = np.abs(np.asarray(jax.device_get(a), np.float64)
                           - np.asarray(jax.device_get(b), np.float64)).max()
                assert d == 0.0, (name, float(d))

        for k, (shape_a, shape_b) in enumerate([((1, 1), (2, 2)),
                                                ((2, 2), (1, 1))]):
            ckdir = f"{os.environ['CK_BASE']}/pair{k}"
            mesh_a = make_mesh(shape_a, ("data", "model"))
            runner = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh_a)
            cm = CheckpointManager(ckdir)
            save_cb = make_vcycle_save_cb(cm, schedule=runner.plan)

            def killing_cb(state, params, opt_state):
                save_cb(state, params, opt_state)
                if state.global_step == 6:  # mid-upward-sweep: stash is live
                    raise Preempted

            try:
                runner.run(ckpt_cb=killing_cb, ckpt_every=2)
                raise AssertionError("kill never fired")
            except Preempted:
                pass
            cm.wait()

            mesh_b = make_mesh(shape_b, ("data", "model"))
            runner2 = VCycleRunner(cfg, ml, tc, bf, seed=0, mesh=mesh_b)
            state, params, opt = restore_vcycle_state(cm, runner2, tc)
            assert (state.phase, state.level, state.global_step) == ("up", 1, 6)
            assert list(state.params_before) == [0]
            # the stash really landed on mesh B...
            leaf = jax.tree.leaves(state.params_before[0])[0]
            assert leaf.sharding.mesh.shape == dict(zip(("data", "model"),
                                                        shape_b))
            # ...and re-sharding changed the VALUES not at all: an unsharded
            # restore of the same checkpoint must agree bit-for-bit
            r_plain = VCycleRunner(cfg, ml, tc, bf, seed=0)
            s0, p0, o0 = restore_vcycle_state(cm, r_plain, tc)
            exact_equal(p0, params, "params")
            exact_equal(o0, opt, "opt")
            exact_equal(s0.params_before[0], state.params_before[0], "stash")

            out = runner2.run(state=state, params=params, opt_state=opt)
            assert out.history.step == ref.history.step
            assert out.history.level == ref.history.level
            for a, b in zip(jax.tree.leaves(out.params),
                            jax.tree.leaves(ref.params)):
                np.testing.assert_allclose(np.asarray(a, np.float64),
                                           np.asarray(b, np.float64),
                                           atol=1e-2)
            np.testing.assert_allclose(out.history.loss, ref.history.loss,
                                       atol=1e-2)
            print(f"pair{k} OK")
        print("CROSS_MESH_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src" + os.pathsep + "tests",
               CK_BASE=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "CROSS_MESH_OK" in out.stdout


@pytest.mark.slow
def test_reduced_dryrun_subprocess(tmp_path):
    """Lower+compile a smoke config on an 8-device placeholder mesh in a
    subprocess (mirrors launch/dryrun.py's bootstrap ordering)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        from repro.configs import get_config
        from repro.distributed import param_shardings, set_mesh_ctx
        from repro.launch.analysis import analyze_compiled, memory_summary
        from repro.launch.mesh import make_mesh
        from repro.models.api import build_model, make_train_step
        from repro.optim import adamw_init_specs
        from repro.param import struct_tree
        from repro.config import TrainConfig
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh((2, 4), ("data", "model"))
        set_mesh_ctx(mesh)
        cfg = get_config("tinyllama-1.1b", smoke=True).replace(
            d_model=64, vocab_size=512)
        tc = TrainConfig(steps=10, warmup_steps=1, batch_size=4, seq_len=32)
        model = build_model(cfg)
        specs = model.specs()
        p = struct_tree(specs, dtype=cfg.param_dtype)
        ps = param_shardings(specs, mesh)
        o_specs = adamw_init_specs(specs, tc)
        os_ = struct_tree(o_specs, dtype=tc.opt_dtype)
        osh = param_shardings(o_specs, mesh)
        batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
        bsh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
        step = make_train_step(model, tc)
        co = jax.jit(step, in_shardings=(ps, osh, bsh)).lower(p, os_, batch).compile()
        rl, colls = analyze_compiled(co, 8, 1.0)
        print(json.dumps({"flops": rl.flops_per_device,
                          "colls": colls["total"]["count"],
                          "mem": memory_summary(co)["peak_bytes_est"]}))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0 and rec["colls"] > 0 and rec["mem"] > 0
