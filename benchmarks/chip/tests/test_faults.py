"""A run with the timed path broken underneath comes out not correct, and so
does the fp8 control put in the program's place.  Tiny sizes on the CPU; the
harness's look for a chip is skipped, everything else is a whole run."""
import time

import jax
import jax.numpy as jnp
import pytest

import harness

TINY_BERT = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                 intermediate_size=256, vocab_size=500)
TINY_TRAIN = dict(rows=8, seq=32, pool=4, ref_rows=4)


def tiny_train_ctx(seed=5):
    w = dict(harness.load_json("workloads", "bert-large.vcycle.json"), **TINY_TRAIN)
    c = dict(harness.load_json("configs", "bert-large.json"), **TINY_BERT)
    return harness.make_ctx("bert-large.vcycle", seed, 0.5, False, time.perf_counter(),
                            workload=w, config=c, require_tpu=False)


def run(ctx):
    res = harness.run_cell(ctx, [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                                 {"name": "setup_s", "unit": "s"}])
    return res, {k: v["value"] for k, v in res["compared"].items()}


def state_unchanged(step):
    """The step runs, but hands back the state it was given."""
    def fn(p, o, b):
        copy = lambda t: jax.tree.map(jnp.copy, t)
        _, _, m = step(copy(p), copy(o), b)
        return p, o, m
    return fn


def half_batch(step):
    """The step sees the first half of the rows; its mean is over those."""
    def fn(p, o, b):
        return step(p, o, jax.tree.map(lambda x: x[: x.shape[0] // 2], b))
    return fn


SMALL = {"scale", "bias", "bq", "bk", "bv", "bo", "b_up", "b_down"}


def small_leaves_frozen(step):
    """The step updates the matrices but hands back every LayerNorm scale
    and bias as it was given."""
    def fn(p, o, b):
        before = jax.tree.map(jnp.copy, p)
        new, o, m = step(p, o, b)
        keep = lambda path, x, y: y if path[-1].key in SMALL else x
        return jax.tree_util.tree_map_with_path(keep, new, before), o, m
    return fn


@pytest.mark.parametrize("fault", [state_unchanged, half_batch, small_leaves_frozen])
def test_broken_train_step_is_not_correct(fault):
    ctx = tiny_train_ctx()
    ctx.faults["step"] = fault
    res, nums = run(ctx)
    assert res["correct"] is False, nums
    if fault is state_unchanged:
        assert nums["update_gap.l0"] == pytest.approx(1.0) and nums["grad_gap.l0"] == pytest.approx(1.0)
    if fault is small_leaves_frozen:
        assert nums["update_gap.l0"] == pytest.approx(1.0)
        assert nums["update_gap.l1"] == pytest.approx(1.0)


def test_fp8_train_step_control_is_not_correct():
    """The reference with every product in fp8 in the program's place fails
    a train-step number (its coalescing is float32, so not that one)."""
    ctx = tiny_train_ctx(seed=6)
    kind = harness.load_module("kinds", "train.py")
    refr = kind.reference_side(ctx.seed, ctx.config, ctx.workload)
    ctrl = kind.reference_side(ctx.seed, ctx.config, ctx.workload, mode="fp8")
    lim = ctx.workload["limits"]
    nums = dict(kind.numbers(ctrl, refr))
    assert nums["coalesce_gap"] == 0.0
    assert any(v > lim[k] for k, v in nums.items() if k != "coalesce_gap"), (nums, lim)


def test_bf16_coalescing_control_is_not_correct():
    ctx = tiny_train_ctx(seed=6)
    kind = harness.load_module("kinds", "train.py")
    compare = harness.load_module("compare.py")
    refr = kind.reference_coalesced(ctx.seed, ctx.config, jnp.float32)
    ctrl = kind.reference_coalesced(ctx.seed, ctx.config, jnp.bfloat16)
    gap = compare.coalesce_gap(ctrl, refr)
    assert gap > ctx.workload["limits"]["coalesce_gap"], gap
