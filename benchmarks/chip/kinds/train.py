"""Training cell: the program's V-cycle train steps at two levels, interleaved.

Set-up builds the ``VCycleRunner`` of the configuration, makes the level-0
weights on the device from the seed (``reference.init_params``), coalesces
them with the program's ``operators.make_coalesce_fn`` into the level-1
start, and makes the masked-LM batches.  It then drives ``step_fn(0)`` and
``step_fn(1)`` through their first ``compare_steps`` steps each, on their own
feed, and keeps what the comparison needs.  The window goes on with the same
objects: level-0 and level-1 steps in the workload's ratio (31:15), spread
evenly, dispatched with at most ``queue`` steps in flight, until
``--seconds`` have passed and every dispatched step has finished.

After the window the program's state is freed and the reference follows the
same first steps from the same seed (``reference_side``).
"""
from __future__ import annotations

import collections
import functools
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

import harness

ref = harness.load_module("reference.py")
layout = harness.load_module("layout.py")
compare = harness.load_module("compare.py")
mlm = harness.load_module("traffic", "mlm.py")
counts = harness.load_module("counts", "model.py")


def level_dims(d: Dict, level: int) -> Dict:
    d = dict(d)
    for _ in range(level):
        d.update(E=d["E"] // 2, H=d["H"] // 2, F=d["F"] // 2, L=(d["L"] + 1) // 2)
    return d


def hyper(w: Dict) -> Dict:
    o = w["optimizer"]
    return {"lr": o["peak_lr"], "b1": o["b1"], "b2": o["b2"], "eps": o["eps"],
            "wd": o["weight_decay"], "clip": o["grad_clip"]}


def schedule(ratio) -> List[int]:
    """Levels of one period, spread evenly (31:15 -> 46 steps)."""
    n0, n1 = ratio
    n = n0 + n1
    return [int((i + 1) * n1 // n > i * n1 // n) for i in range(n)]


def data(seed: int, w: Dict, d: Dict):
    """Both levels' batch pools, level 0 first, as lists of batches."""
    key = jax.random.fold_in(ref.seed_key(seed), 1)
    n = w["pool"]
    pool = mlm.batches(key, n=2 * n, rows=w["rows"], seq=w["seq"], vocab=d["V"],
                       mask_id=w["mask_id"], mask_rate=w["mask_rate"])
    split = jax.jit(lambda b: [jax.tree.map(lambda x: x[i], b) for i in range(2 * n)])(pool)
    return split[:n], split[n:]


def weight_key(seed: int):
    return jax.random.fold_in(ref.seed_key(seed), 0)


@jax.jit
def _norms(tree_ref):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in layout.leaves(tree_ref).items()}


def _floats(tree) -> Dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


# ---------------------------------------------------------------------------
# the program


def program_side(ctx):
    """Builds the runner and state, runs the first steps of both levels, and
    returns (objects for the window, readings for the comparison)."""
    from repro.config import MultiLevelConfig, TrainConfig
    from repro.core import operators as ops
    from repro.core.vcycle import VCycleRunner
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
    # The depth half of the operator is an einsum, which the v5e compile at
    # default precision runs on a bfloat16 copy of the float32 weights; the
    # weights are stated float32, so the operator runs at that precision.
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
        for i in range(n):
            params[lv], opts[lv], m = steps[lv](params[lv], opts[lv], pools[lv][i])
            losses.append(m["loss"])
            if i == 0:
                g = grad_norms(opts[lv]["m"])
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


def window(ctx, st) -> Dict:
    """The measured window; returns what it completed."""
    w = ctx.workload
    sched = schedule(w["ratio"])
    params, opts, pools, steps, done = st["params"], st["opts"], st["pools"], st["steps"], st["done"]
    inflight = collections.deque()
    levels = []
    t0 = time.perf_counter()
    k = 0
    while True:
        lv = sched[k % len(sched)]
        batch = pools[lv][done[lv] % len(pools[lv])]
        params[lv], opts[lv], m = steps[lv](params[lv], opts[lv], batch)
        done[lv] += 1
        k += 1
        levels.append(lv)
        inflight.append(m["loss"])
        if len(inflight) > w["queue"]:
            inflight.popleft().block_until_ready()
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    jax.block_until_ready((list(inflight), params, opts))
    t1 = time.perf_counter()
    return {"t0": t0, "t1": t1, "levels": levels}


# ---------------------------------------------------------------------------
# the reference


@functools.partial(jax.jit, static_argnames=("causal", "mode", "rows", "hp", "n"))
def _ref_steps(p0, batches, *, causal, mode, rows, hp, n):
    hp = dict(hp)
    p, state = p0, ref.adam_state(p0)
    losses = []
    for i in range(n):
        loss, grad = ref.loss_and_grad(p, batches[i], causal, mode, rows)
        p, state, clipped = ref.adamw(p, grad, state, hp)
        losses.append(loss)
        if i == 0:
            g = _norms(clipped)
    upd = _norms(jax.tree.map(jnp.subtract, p, p0))
    return jnp.stack(losses), g, upd


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


def reference_coalesced(seed: int, config: Dict, dtype) -> Dict:
    """The reference's coalescing of the seed's weights with its pair sums
    taken in ``dtype``: bfloat16 is the coalescing control."""
    d = ref.dims(config)
    p0 = jax.jit(lambda k: ref.init_params(k, d))(weight_key(seed))
    c = jax.jit(functools.partial(ref.coalesce, dtype=dtype))(p0)
    return layout.leaves(jax.device_get(c))


def numbers(prog: Dict, refr: Dict) -> List:
    nums = compare.training(prog["l0"], refr["l0"], "l0")
    nums += compare.training(prog["l1"], refr["l1"], "l1")
    nums.append(("coalesce_gap", compare.coalesce_gap(prog["coalesced"], refr["coalesced"])))
    return nums


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
        ctx.tracer.reduce()
    del st
    refr = reference_side(ctx.seed, ctx.config, w)
    nums = numbers(readings, refr)
    lim = w["limits"]
    return {"e2e": {"train_tokens_per_s": tokens / window_s, "setup_s": setup_s},
            "compared": [(k, v, lim[k]) for k, v in nums],
            "attempted": n_steps, "failed": 0, "memory_peak_bytes": mem}
