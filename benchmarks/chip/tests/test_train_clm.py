"""The causal-LM training cell at a tiny GPT size on the CPU: a whole run
comes out correct against ``reference.py``, and a broken step does not.
Also its batches, and its reader of the flash kernels in a trace."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from test_faults import half_batch, run, small_leaves_frozen, state_unchanged

kind = harness.load_module("kinds", "train_clm.py")
clm = harness.load_module("traffic", "clm.py")

# GPT-2's key names at a tiny size; 256 tokens over 64-key blocks take the
# program's flash path (flash_xla on the CPU) rather than plain attention.
# The program computes in float32 here: the limits are set from bfloat16
# readings at the cell's full size, and bfloat16 rounding weighs more in a
# model 12 times narrower, so this checks the kind's wiring, and the faults
# below that it catches, without that rounding.
TINY_GPT = dict(n_embd=64, n_layer=2, n_head=4, n_inner=None, vocab_size=500,
                overrides={"attn_block_k": 64, "compute_dtype": jnp.float32})
TINY_TRAIN = dict(rows=4, seq=256, pool=4, ref_rows=2)


def tiny_clm_ctx(seed=5):
    w = dict(harness.load_json("workloads", "gpt-base.vcycle.json"), **TINY_TRAIN)
    c = dict(harness.load_json("configs", "gpt-base.json"), **TINY_GPT)
    return harness.make_ctx("gpt-base.vcycle", seed, 0.5, False, time.perf_counter(),
                            workload=w, config=c, require_tpu=False)


def test_batches_label_the_next_token():
    b = clm.batches(jax.random.PRNGKey(3), n=2, rows=3, seq=16, vocab=50)
    toks, labels = np.asarray(b["tokens"]), np.asarray(b["labels"])
    assert toks.shape == labels.shape == (2, 3, 16)
    assert (toks >= 0).all() and (toks < 50).all()
    np.testing.assert_array_equal(labels[..., :-1], toks[..., 1:])
    assert (labels[..., -1] == -1).all()
    # the chain of traffic/mlm.py: each token has at most `branch` successors
    succ = {}
    for row in toks.reshape(-1, 16):
        for a, b_ in zip(row[:-1], row[1:]):
            succ.setdefault(int(a), set()).add(int(b_))
    assert max(len(s) for s in succ.values()) <= 4


def test_tiny_clm_run_is_correct():
    ctx = tiny_clm_ctx()
    res, nums = run(ctx)
    assert res["correct"] is True, nums
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert nums["coalesce_gap"] < 1e-5


@pytest.mark.parametrize("fault", [state_unchanged, half_batch, small_leaves_frozen])
def test_broken_clm_step_is_not_correct(fault):
    ctx = tiny_clm_ctx()
    ctx.faults["step"] = fault
    res, nums = run(ctx)
    assert res["correct"] is False, nums


def test_fp8_clm_control_is_not_correct():
    ctx = tiny_clm_ctx(seed=6)
    refr = kind.reference_side(ctx.seed, ctx.config, ctx.workload)
    ctrl = kind.reference_side(ctx.seed, ctx.config, ctx.workload, mode="fp8")
    lim = ctx.workload["limits"]
    nums = dict(kind.numbers(ctrl, refr))
    assert any(v > lim[k] for k, v in nums.items() if k != "coalesce_gap"), (nums, lim)


# ---------------------------------------------------------------------------
# the flash kernels in a trace


def ms(x):
    return x * 1e6  # ns


def flash_op(kernel, phase="jvp()"):
    return (f"jit(train_step_l0)/{phase}/while/body/closed_call/checkpoint/attention/"
            f"{kernel}/pallas_call:", "custom-call")


def hand_events():
    ops = [
        flash_op("flash_attention_fwd") + (ms(1), ms(1)),
        ("jit(train_step_l0)/jvp()/while/body/closed_call/attention/dot_general:",
         "convolution fusion", ms(2), ms(1)),
        flash_op("flash_attention_fwd", "transpose(jvp())") + (ms(3), ms(1)),
        flash_op("flash_attention_dq", "transpose(jvp())") + (ms(4), ms(2)),
        flash_op("flash_attention_dkv", "transpose(jvp())") + (ms(6), ms(3)),
        # level 1, and a level-0 step after the window
        flash_op("flash_attention_fwd").__class__(
            ("jit(train_step_l1)/jvp()/flash_attention_fwd/pallas_call:", "custom-call",
             ms(21), ms(0.5))),
        flash_op("flash_attention_fwd") + (ms(101), ms(1)),
    ]
    modules = [("jit_train_step_l0(7)", ms(0), ms(10)), ("jit_train_step_l1(8)", ms(20), ms(2)),
               ("jit_train_step_l0(7)", ms(30), ms(10)), ("jit_train_step_l0(7)", ms(100), ms(10))]
    host = [("bench.window", ms(0), ms(50), {})]
    return {"ops": ops, "modules": modules, "host": host}


def test_flash_kernels_by_hand():
    f = kind.flash_kernels(hand_events())
    assert f["steps"] == 2
    assert f["calls"] == {"flash_attention_fwd": 2, "flash_attention_dq": 1,
                          "flash_attention_dkv": 1}
    assert f["ms"] == pytest.approx({"flash_attention_fwd": 2.0, "flash_attention_dq": 2.0,
                                     "flash_attention_dkv": 3.0})
    assert kind.flash_kernels(hand_events(), level=1)["calls"]["flash_attention_fwd"] == 1
    with pytest.raises(ValueError):
        kind.flash_kernels(hand_events(), window="bench.nothing")


def test_flash_metrics_fall_silent_without_the_kernels():
    """A program without the named kernels (flash_xla, or the parent of
    this cell) reports neither metric, and raises nothing."""
    ms_metric = harness.load_module("metrics", "flash_ms.l0.train.py")
    roof = harness.load_module("metrics", "flash_roofline.l0.train.py")
    ev = hand_events()
    ev["ops"] = [o for o in ev["ops"] if "flash" not in o[0]]
    ctx = tiny_clm_ctx()
    ctx.counters["flash"] = dict(kind.flash_kernels(ev), shape={})
    assert ms_metric.read(ctx) is None and roof.read(ctx) is None
    ctx.counters.clear()
    assert ms_metric.read(ctx) is None and roof.read(ctx) is None


def test_flash_metrics_by_hand():
    """Two level-0 steps of the cell: 24 forward calls and 12 backward pairs
    a step (remat's second forward), at the level-0 shape."""
    ms_metric = harness.load_module("metrics", "flash_ms.l0.train.py")
    roof = harness.load_module("metrics", "flash_roofline.l0.train.py")
    ctx = tiny_clm_ctx()
    ctx.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    calls = {"flash_attention_fwd": 48, "flash_attention_dq": 24, "flash_attention_dkv": 24}
    ctx.counters["flash"] = {"steps": 2, "calls": calls, "shape": dict(
        bh=16 * 12, s=1024, t=1024, d=64, causal=True),
        "ms": {"flash_attention_fwd": 20.0, "flash_attention_dq": 30.0,
               "flash_attention_dkv": 50.0}}
    assert ms_metric.read(ctx) == pytest.approx(50.0)
    # 7.0707 ms of attention work a step, over 50 ms of kernel time a step
    assert roof.read(ctx) == pytest.approx(100 * 7.0707 / 50.0, rel=1e-4)
    calls["flash_attention_dkv"] = 23
    with pytest.raises(ValueError, match="unpaired"):
        roof.read(ctx)


# a TPU v5e run of gpt-base.vcycle, `--seconds 1 --trace 1`, seed 3416000001,
# with the Pallas flash kernels at 128 x 128 tiles: 4 level-0 and 1 level-1 steps
TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "gpt-base.vcycle.1s.xplane.pb.gz")


def test_flash_kernels_on_recorded_trace():
    """12 layers a step: two forwards (remat's second), one dq, one dk/dv."""
    sr = harness.load_module("scope_reduce.py")
    ev = sr.read(TRACE)
    f = kind.flash_kernels(ev)
    assert f["steps"] == 4
    assert f["calls"] == {"flash_attention_fwd": 96, "flash_attention_dq": 48,
                          "flash_attention_dkv": 48}
    assert f["ms"] == pytest.approx({"flash_attention_fwd": 536.825, "flash_attention_dq": 227.634,
                                     "flash_attention_dkv": 301.196}, abs=1e-3)
    assert kind.flash_kernels(ev, level=1)["calls"] == {
        "flash_attention_fwd": 12, "flash_attention_dq": 6, "flash_attention_dkv": 6}
