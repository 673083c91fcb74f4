"""Causal-LM training cell: ``kinds/train.py``'s V-cycle train steps at two
levels, interleaved, on next-token batches (``traffic/clm.py``).

Everything that does not draw batches is ``train.py``'s own: the level
sizes, the optimizer's hyper-parameters, the schedule, the weights, the
window, the reference's steps and the numbers that decide ``correct``.  This
module repeats only ``data``, ``program_side``, ``reference_side`` and
``run`` with the causal batches.  ``run`` also logs which implementation
each level's step traced (``dispatch.traced()``), and, when traced, reads
the flash kernels' calls and device time in the level-0 steps from the
trace (``flash_kernels``) into ``ctx.counters["flash"]``.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
from typing import Dict, List

import jax
import jax.numpy as jnp

import harness

train = harness.load_module("kinds", "train.py")
ref = harness.load_module("reference.py")
layout = harness.load_module("layout.py")
clm = harness.load_module("traffic", "clm.py")
counts = harness.load_module("counts", "model.py")
scope_reduce = harness.load_module("scope_reduce.py")

level_dims, hyper, schedule, weight_key = (train.level_dims, train.hyper, train.schedule,
                                           train.weight_key)
window, numbers, reference_coalesced = train.window, train.numbers, train.reference_coalesced
_norms, _floats, _ref_steps = train._norms, train._floats, train._ref_steps

FLASH = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")


def data(seed: int, w: Dict, d: Dict):
    """Both levels' batch pools, level 0 first, as lists of batches."""
    key = jax.random.fold_in(ref.seed_key(seed), 1)
    n = w["pool"]
    pool = clm.batches(key, n=2 * n, rows=w["rows"], seq=w["seq"], vocab=d["V"],
                       branch=w["branch"])
    split = jax.jit(lambda b: [jax.tree.map(lambda x: x[i], b) for i in range(2 * n)])(pool)
    return split[:n], split[n:]


# ---------------------------------------------------------------------------
# the program


def program_side(ctx):
    """Builds the runner and state, runs the first steps of both levels, and
    returns (objects for the window, readings for the comparison)."""
    from repro.config import MultiLevelConfig, TrainConfig
    from repro.core import operators as ops
    from repro.core.vcycle import VCycleRunner
    from repro.kernels import dispatch
    from repro.optim import adamw_init

    w, d = ctx.workload, ref.dims(ctx.config)
    cfg = layout.program_config(ctx.config)
    o = w["optimizer"]
    tc = TrainConfig(steps=o["steps"], warmup_steps=o["warmup_steps"], peak_lr=o["peak_lr"],
                     schedule=o["schedule"], weight_decay=o["weight_decay"], b1=o["b1"],
                     b2=o["b2"], eps=o["eps"], grad_clip=o["grad_clip"],
                     batch_size=w["rows"], seq_len=w["seq"], seed=ctx.seed)
    ml = MultiLevelConfig(**w["vcycle"])
    runner = VCycleRunner(cfg, ml, tc, batch_fn=lambda g: None, seed=ctx.seed)
    layout.check_shapes(runner.specs[0], jax.eval_shape(
        lambda k: ref.init_params(k, d), weight_key(0)))
    layout.check_shapes(runner.specs[1], jax.eval_shape(
        lambda k: ref.init_params(k, level_dims(d, 1)), weight_key(0)))

    init = jax.jit(lambda k: layout.to_program(ref.init_params(k, d)))
    p0 = init(weight_key(ctx.seed))
    coalesce = ops.make_coalesce_fn(runner.specs[0], cfg, ml, plan=runner.proj_plans[0])
    # as in train.py: the operator runs at the weights' stated precision
    with jax.default_matmul_precision("highest"):
        p1 = coalesce(p0)
    coalesced = jax.device_get(layout.from_program(p1))
    opt_init = jax.jit(functools.partial(adamw_init, tc=tc))
    params, opts = [p0, p1], [opt_init(p0), opt_init(p1)]
    pools = data(ctx.seed, w, d)
    steps = [runner.step_fn(0), runner.step_fn(1)]
    fault = ctx.faults.get("step")  # tests plant a broken step here
    if fault is not None:
        steps = [fault(s) for s in steps]
    b1 = o["b1"]
    grad_norms = jax.jit(lambda m: _norms(jax.tree.map(
        lambda x: x / (1 - b1), layout.from_program(m))))
    delta_init = jax.jit(lambda p, k: _norms(layout.from_program(jax.tree.map(
        jnp.subtract, p, init(k)))))
    delta_base = jax.jit(lambda p, q: _norms(layout.from_program(jax.tree.map(
        jnp.subtract, p, q))))

    readings = {}
    n = w["compare_steps"]
    for lv in (0, 1):
        losses = []
        dispatch.reset_traced()
        for i in range(n):
            params[lv], opts[lv], m = steps[lv](params[lv], opts[lv], pools[lv][i])
            losses.append(m["loss"])
            if i == 0:
                g = grad_norms(opts[lv]["m"])
        ctx.log(f"level {lv} step traced with {dispatch.traced()}")
        if lv == 0:
            upd = delta_init(params[0], weight_key(ctx.seed))
        else:
            upd = delta_base(params[1], layout.to_program(coalesced))
        readings[f"l{lv}"] = {"loss": [float(x) for x in losses], "grad": _floats(g),
                              "update": _floats(upd)}
    readings["coalesced"] = layout.leaves(coalesced)
    state = {"runner": runner, "cfg": cfg, "params": params, "opts": opts,
             "pools": pools, "steps": steps, "done": [n, n]}
    return state, readings


# ---------------------------------------------------------------------------
# the reference


def reference_side(seed: int, config: Dict, w: Dict, mode: str = "f32",
                   half: bool = False) -> Dict:
    """Readings of the reference over the same first steps from the same
    seed, each level from the float32 coalescing.  ``mode="fp8"`` is the
    train-step control; ``half=True`` plants the fault "half of the batch
    left out"."""
    d = ref.dims(config)
    n = w["compare_steps"]
    hp = tuple(sorted(hyper(w).items()))
    pools = data(seed, w, d)
    rows = w["ref_rows"]
    out = {}
    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(lambda k: ref.init_params(k, d))(weight_key(seed))
        c = jax.jit(ref.coalesce)(p0)
        for lv, start in ((0, p0), (1, c)):
            bs = pools[lv][:n]
            if half:
                bs = [jax.tree.map(lambda x: x[:x.shape[0] // 2], b) for b in bs]
            losses, g, upd = _ref_steps(start, bs, causal=d["causal"], mode=mode,
                                        rows=min(rows, bs[0]["tokens"].shape[0]), hp=hp, n=n)
            out[f"l{lv}"] = {"loss": [float(x) for x in losses], "grad": _floats(g),
                             "update": _floats(upd)}
            del start
        out["coalesced"] = layout.leaves(jax.device_get(c))
    return out


# ---------------------------------------------------------------------------
# the flash kernels in the trace


def _kernel(tf_op: str):
    """The flash kernel whose ``pallas_call`` an operation is, or None: its
    name stack holds the kernel's name as a component."""
    stack = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    for part in stack.split("/"):
        if part in FLASH:
            return part
    return None


def flash_kernels(ev: Dict[str, List], level: int = 0, window: str = "bench.window") -> Dict:
    """From ``scope_reduce.read``'s events: the level's train-step programs
    in the window (``steps``) and, per flash kernel, its calls and device ms
    inside them.  A program without the named kernels gives zero calls."""
    wins = [(s, s + d) for n, s, d, _ in ev["host"] if n == window]
    if not wins:
        raise ValueError(f"no host annotation {window!r} in the trace")
    w0, w1 = wins[0]
    ops = sorted(ev["ops"], key=lambda o: o[2])
    starts = [o[2] for o in ops]
    prefix = f"jit_train_step_l{level}("
    out = {"steps": 0, "calls": dict.fromkeys(FLASH, 0), "ms": dict.fromkeys(FLASH, 0.0)}
    for name, s, d in ev["modules"]:
        if not name.startswith(prefix) or not (s < w1 and s + d > w0):
            continue
        out["steps"] += 1
        for tf_op, _, _, od in ops[bisect.bisect_left(starts, s):bisect.bisect_left(starts, s + d)]:
            k = _kernel(tf_op)
            if k is not None:
                out["calls"][k] += 1
                out["ms"][k] += od * 1e-6
    return out


def read_flash(ctx) -> Dict:
    """``flash_kernels`` of the level-0 steps of this run's trace, with the
    shape of their calls; read before ``Trace.reduce`` deletes the file."""
    path = glob.glob(os.path.join(ctx.tracer.dir, "**", "*.xplane.pb"), recursive=True)[0]
    f = flash_kernels(scope_reduce.read(path))
    d, w = ref.dims(ctx.config), ctx.workload
    f["shape"] = {"bh": w["rows"] * d["H"], "s": w["seq"], "t": w["seq"], "d": d["D"],
                  "causal": d["causal"]}
    return f


# ---------------------------------------------------------------------------


def run(ctx) -> Dict:
    w = ctx.workload
    compiles = harness.Compiles()
    st, readings = program_side(ctx)
    ctx.log(f"set-up: first {w['compare_steps']} steps at each level; losses "
            f"l0 {readings['l0']['loss']} l1 {readings['l1']['loss']}")
    ctx.tracer = harness.Trace(ctx.trace, ctx.keep_trace)
    n_before = compiles.n
    with ctx.tracer:
        win = window(ctx, st)
    in_window = compiles.n - n_before
    setup_s = win["t0"] - ctx.t_start
    mem = harness.memory_peak()
    n_steps = len(win["levels"])
    tokens = n_steps * w["rows"] * w["seq"]
    window_s = win["t1"] - win["t0"]
    ctx.log(f"window: {n_steps} steps ({win['levels'].count(0)} level 0, "
            f"{win['levels'].count(1)} level 1) in {window_s:.4f} s; "
            f"compilations inside the window: {in_window}")
    d = ref.dims(ctx.config)
    ctx.counters.update(
        levels=win["levels"], window_s=window_s, compiles_in_window=in_window,
        step_flops=[counts.train_step_flops(level_dims(d, i), w["rows"], w["seq"])
                    for i in (0, 1)])
    if ctx.trace:
        ctx.counters["flash"] = f = read_flash(ctx)
        ctx.log(f"flash kernels in {f['steps']} level-0 steps: calls {f['calls']}, "
                f"device ms {f['ms']}")
        ctx.tracer.reduce()
    del st
    refr = reference_side(ctx.seed, ctx.config, w)
    nums = numbers(readings, refr)
    lim = w["limits"]
    return {"e2e": {"train_tokens_per_s": tokens / window_s, "setup_s": setup_s},
            "compared": [(k, v, lim[k]) for k, v in nums],
            "attempted": n_steps, "failed": 0, "memory_peak_bytes": mem}
