"""V-cycle training process (paper Algorithm 1) + generic training loop with
FLOPs-indexed loss history (the paper's evaluation axis).

The runner is production-shaped: per-level compiled steps are built once and
cached; level transitions are jitted and host-round-trip-free, with the
"stack"-variant width projections and the interpolation running matrix-free
through the kernel registry (repro.kernels.dispatch: Pallas on TPU, fused XLA
elsewhere); the optimizer is re-initialized at transitions (paper §Discussion
/ App. C); and
the whole V-cycle state (level, phase, step) is checkpointable via
``repro.checkpoint`` (see launch/train.py).

The runner is an explicit state machine, not a straight-line script:

* ``segments(cfg, ml, tc)`` materializes Algorithm 1 as a deterministic
  schedule of :class:`SegmentPlan` entries -- the downward sweep (init-train
  ``E_a`` per level, then coalesce), the upward sweep (train ``E_small``, then
  de-coalesce + interpolate) and the final full-size segment.
* :class:`VCycleState` carries everything needed to re-enter training at an
  arbitrary (phase, level, step): segment index, step-within-segment, global
  step, cumulative FLOPs, the :class:`History`, and the ``params_before``
  stash consumed by Interpolation on the way back up.  Together with the
  deterministic ``batch_fn(global_step)`` data order this makes mid-cycle
  checkpoint/resume bit-identical to an uninterrupted run (see
  ``launch/train.py`` for the save/restore wiring and ``tests/test_resume.py``
  for the equivalence proof).
* :class:`VCycleRunner` owns the per-level compiled-step cache: each level's
  train step is ``jax.jit``-compiled at most once per run even though every
  level below the top is visited twice (down + up sweep); ``n_compiles``
  exposes the count for tests.  Built with a ``mesh``, the runner shards the
  whole cycle: per-level explicit ``in_shardings``/``out_shardings`` train
  steps and sharded-in/sharded-out level transitions (the launcher's
  ``--mesh`` flag feeds this; checkpoints stay mesh-agnostic, so restores
  may re-shard).
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, MultiLevelConfig, TrainConfig
from repro.core import flops as flops_lib
from repro.core import operators as ops
from repro.core import plans as plans_lib
from repro.models.api import Model, build_model, make_train_step
from repro.optim import adamw_init


@dataclasses.dataclass
class History:
    """Loss trace indexed by cumulative training FLOPs."""

    flops: List[float] = dataclasses.field(default_factory=list)
    loss: List[float] = dataclasses.field(default_factory=list)
    step: List[int] = dataclasses.field(default_factory=list)
    level: List[int] = dataclasses.field(default_factory=list)

    def log(self, f: float, l: float, s: int, lv: int):
        self.flops.append(float(f))
        self.loss.append(float(l))
        self.step.append(int(s))
        self.level.append(int(lv))

    def smoothed(self, window: int = 5) -> Tuple[np.ndarray, np.ndarray]:
        lo = np.asarray(self.loss)
        fl = np.asarray(self.flops)
        if len(lo) < window:
            return fl, lo
        kernel = np.ones(window) / window
        sm = np.convolve(lo, kernel, mode="valid")
        return fl[window - 1:], sm

    def to_dict(self) -> Dict[str, list]:
        # copies, not views: async checkpoint writers serialize this dict on a
        # background thread while the training loop keeps appending
        return {"flops": list(self.flops), "loss": list(self.loss),
                "step": list(self.step), "level": list(self.level)}


def flops_to_reach(hist: History, target: float, window: int = 5) -> Optional[float]:
    """First cumulative-FLOPs point where the smoothed loss crosses ``target``."""
    fl, sm = hist.smoothed(window)
    idx = np.nonzero(sm <= target)[0]
    return float(fl[idx[0]]) if len(idx) else None


def saving_vs_baseline(base: History, ours: History, window: int = 5) -> Dict[str, float]:
    """The paper's headline metric: FLOPs saving at the baseline's final quality."""
    _, sm = base.smoothed(window)
    target = float(sm[-1])
    f_base = flops_to_reach(base, target, window) or base.flops[-1]
    f_ours = flops_to_reach(ours, target, window)
    if f_ours is None:
        return {"target_loss": target, "flops_saving": float("nan"),
                "base_flops": f_base, "ours_flops": float("nan")}
    return {"target_loss": target, "flops_saving": 1.0 - f_ours / f_base,
            "base_flops": f_base, "ours_flops": f_ours}


# ---------------------------------------------------------------------------
# generic training segment


def _train_loop(step_fn, batch_fn, steps: int, start_in_seg: int, params,
                opt_state, history: History, cum: float, g: int, level: int,
                fps: float, log_every: int, target_loss: Optional[float],
                on_step=None, sync_every_step: bool = False):
    """The one segment inner loop (shared by ``train_segment`` and
    ``VCycleRunner``, so log cadence, FLOPs accounting and the smoothed
    target-loss early stop cannot drift apart between the baselines and the
    V-cycle).

    ``g`` is the global step (keys the deterministic ``batch_fn``); ``i``
    indexes within the segment (keys the log cadence), starting at
    ``start_in_seg`` when resuming.  ``on_step(i, params, opt_state, cum, g,
    stop, dt)`` fires after each step's bookkeeping with the step's measured
    wall time -- the runner hangs state mirroring, checkpoint hooks and the
    watchdog heartbeat there (``stop`` is the target-loss early exit, which a
    checkpoint must not capture: the stop decision is not part of the
    persisted state, so resuming from the stopping step would train past it).
    ``sync_every_step`` blocks on the loss each step so dt is an honest step
    time (same rationale as ``train_plain``: a straggler on a non-log step
    must be seen, and dt must not absorb checkpoint snapshots) -- callers
    without a dt consumer leave it off and keep async-dispatch pipelining.

    The target-loss window covers the CURRENT segment's entries only -- the
    global history mixes in the previous (smaller) level's losses, and
    smoothing across a level boundary can fire a spurious early exit.
    Segment membership is recovered from ``history.step`` (entries newer than
    the segment's starting global step), so a mid-segment resume sees the
    same window as an uninterrupted run.  The original >=5-total-entries
    noise gate is kept, so a fresh run still never stops on its first noisy
    losses; within a V-cycle the window right after a level boundary may
    hold fewer than 5 in-segment samples, and firing on the available mean
    is the pre-existing pinned behavior (tests/test_resume.py).
    """
    seg_base = bisect.bisect_right(history.step, g - start_in_seg)
    for i in range(start_in_seg, steps):
        batch = batch_fn(g)
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if sync_every_step:
            jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        cum += fps
        g += 1
        stop = False
        if i % log_every == 0 or i == steps - 1:
            loss = float(metrics["loss"])
            history.log(cum, loss, g, level)
            if target_loss is not None and len(history.loss) >= 5:
                seg_loss = np.asarray(history.loss[seg_base:])
                w = min(5, len(seg_loss))
                if w and float(seg_loss[-w:].mean()) <= target_loss:
                    stop = True
        if on_step is not None:
            on_step(i, params, opt_state, cum, g, stop, dt)
        if stop:
            break
    return params, opt_state, cum, g


def train_segment(
    model: Model,
    tc: TrainConfig,
    batch_fn: Callable[[int], Dict[str, jax.Array]],
    steps: int,
    *,
    params=None,
    opt_state=None,
    history: Optional[History] = None,
    start_flops: float = 0.0,
    start_step: int = 0,
    level: int = 0,
    seed: int = 0,
    target_loss: Optional[float] = None,
    step_fn=None,
):
    """Train ``model`` for ``steps`` optimizer steps, logging (flops, loss)."""
    history = history if history is not None else History()
    if params is None:
        params = model.init(jax.random.PRNGKey(seed))
    if opt_state is None:
        opt_state = adamw_init(params, tc)
    if step_fn is None:
        step_fn = jax.jit(make_train_step(model, tc), donate_argnums=(0, 1))
    specs = model.specs()
    fps = flops_lib.train_step_flops(model.cfg, specs, tc.batch_size, tc.seq_len)
    params, opt_state, cum, g = _train_loop(
        step_fn, batch_fn, steps, 0, params, opt_state, history,
        start_flops, start_step, level, fps, tc.log_every, target_loss)
    return params, opt_state, history, cum, g


def _host_span(fn: Callable, level: int) -> Callable:
    """``fn`` with each call inside the host span ``repro.train_step``
    (``level`` as its argument), on the profiler's clock; arguments,
    donation and outputs pass through.  ``__wrapped__`` is ``fn``."""

    @functools.wraps(fn)
    def step(*args):
        with jax.profiler.TraceAnnotation("repro.train_step", level=level):
            return fn(*args)

    return step


# ---------------------------------------------------------------------------
# the V-cycle (Algorithm 1) as an explicit, checkpointable state machine


@dataclasses.dataclass
class VCycleOutput:
    params: Any
    history: History
    configs: List[ModelConfig]
    total_flops: float


@dataclasses.dataclass(frozen=True)
class SegmentPlan:
    """One training segment of Algorithm 1.

    The transition *after* a segment is implied by its phase: ``down`` stashes
    ``params_before[level]`` and coalesces to ``level + 1``; ``up``
    de-coalesces to ``level - 1`` and interpolates with the stash; ``final``
    has no successor.
    """

    phase: str  # "down" | "up" | "final"
    level: int
    steps: int


def segments(cfg: ModelConfig, ml: MultiLevelConfig, tc: TrainConfig,
             *, final_steps: Optional[int] = None) -> List[SegmentPlan]:
    """Deterministic segment schedule for Algorithm 1.

    Step budgets follow the paper: E_a = warmup-sized init segment per level
    before coalescing; E_small = one half of the full cycle for every level
    below the top; the top level then trains until convergence (``tc.steps``
    or ``final_steps``, optionally cut short by a target loss).  ``cfg`` is
    part of the signature so per-architecture budget rules can slot in without
    changing call sites.
    """
    del cfg  # schedule currently depends only on (ml, tc)
    K = ml.n_levels
    E_a = max(int(round(tc.steps * ml.e_a_frac)), 1)
    E_small = max(int(round(tc.steps * ml.e_small_frac)), 1)
    plan = [SegmentPlan("down", l, E_a) for l in range(K - 1)]
    plan += [SegmentPlan("up", l, E_small) for l in range(K - 1, 0, -1)]
    plan.append(SegmentPlan("final", 0,
                            final_steps if final_steps is not None else tc.steps))
    return plan


@dataclasses.dataclass
class VCycleState:
    """Everything needed to re-enter ``VCycleRunner.run`` at an arbitrary
    (phase, level, step).

    ``seg_index``/``seg_step`` address the position in the segment schedule
    (``seg_step`` counts completed optimizer steps *within* the current
    segment, so logging cadence and the post-segment transition replay
    identically on resume); ``params_before`` maps level -> the stashed
    pre-coalesce params that Interpolation consumes on the upward sweep.
    ``phase``/``level`` duplicate the schedule entry for checkpoint metadata
    and log lines.
    """

    phase: str = "down"
    level: int = 0
    seg_index: int = 0
    seg_step: int = 0
    global_step: int = 0
    cum_flops: float = 0.0
    history: History = dataclasses.field(default_factory=History)
    params_before: Dict[int, Any] = dataclasses.field(default_factory=dict)
    # carried gradient-reduction state (EF residuals) for the CURRENT level's
    # shapes; None when the strategy is stateless or not yet initialized.
    # Reset (not re-projected) at level transitions: the residual is bounded
    # by half a quantization step and the optimizer re-initializes there
    # anyway, so dropping it introduces no bias -- re-projecting sub-ULP
    # noise through the coalesce operators would be complexity for nothing.
    ef: Any = None


class VCycleRunner:
    """Checkpointable driver for Algorithm 1.

    Owns the per-level model stack and a per-level compiled train-step cache:
    each level's step is built and ``jax.jit``-compiled at most once per run
    even though levels below the top are visited twice (down + up sweep).
    ``run`` may be entered fresh or from a restored :class:`VCycleState`; a
    ``ckpt_cb(state, params, opt_state)`` hook fires every ``ckpt_every``
    global steps (the launcher plugs ``repro.checkpoint`` in there), and an
    ``on_step(state, params, opt_state, stopping, dt)`` hook fires on EVERY
    step with the measured step time (the launcher hangs its watchdog
    heartbeat and SIGTERM preemption check there).

    With ``mesh`` set, the runner is mesh-parallel end to end: each level's
    train step jits with explicit ``in_shardings``/``out_shardings`` (params
    and optimizer from the level's Spec tree via the logical-axis rules, the
    batch data-sharded over the data axes) plus donation, and the level
    transitions (coalesce / de-coalesce+interpolate) run sharded-in,
    sharded-out onto the TARGET level's layout.  Because checkpoints store
    logical (unsharded) arrays, a state saved under one mesh restores onto a
    runner built with another (see ``launch/train.py``).
    """

    def __init__(self, cfg: ModelConfig, ml: MultiLevelConfig, tc: TrainConfig,
                 batch_fn: Callable[[int], Dict[str, jax.Array]], *,
                 seed: int = 0, target_loss: Optional[float] = None,
                 final_steps: Optional[int] = None, verbose: bool = False,
                 mesh=None, drain_flag=None, grad_reduce=None):
        self.ml, self.tc, self.batch_fn = ml, tc, batch_fn
        self.seed, self.target_loss, self.verbose = seed, target_loss, verbose
        self.mesh = mesh
        # a distributed.FusedDrainFlag: the preemption drain OR is computed
        # INSIDE each level's compiled step (one extra tiny input + metrics
        # scalar) instead of a dedicated per-step process_allgather
        self.drain_flag = drain_flag if mesh is not None else None
        # pluggable gradient reduction (distributed/reduce.py): pass a strategy
        # explicitly or let tc.grad_compression name one; either way the
        # per-level steps become shard_map'd with the reduction injected
        if grad_reduce is None and mesh is not None:
            from repro.distributed import make_grad_reduce

            grad_reduce = make_grad_reduce(tc.grad_compression, mesh)
        if grad_reduce is not None and mesh is None:
            raise ValueError("grad_reduce requires a mesh")
        self.grad_reduce = grad_reduce
        # one ProjectionPlan per level transition: proj_plans[l] is the
        # explicit family contract for level l <-> l+1 (which axes halve,
        # which are protected, the role overrides, the carried MoE scalars).
        # self.cfgs derives from the plans so config halving and the maps the
        # transitions apply can never disagree.  NB ``self.plan`` (no s) is
        # the *segment schedule* -- a different thing, and external consumers
        # (benchmarks) read it by that name.
        self.cfgs = [cfg]
        self.proj_plans = []
        for _ in range(ml.n_levels - 1):
            p = plans_lib.build_plan(self.cfgs[-1], ml)
            self.proj_plans.append(p)
            self.cfgs.append(p.small_cfg)
        self.models = [build_model(c) for c in self.cfgs]
        self.specs = [m.specs() for m in self.models]
        self.plan = segments(cfg, ml, tc, final_steps=final_steps)
        if verbose:
            for p in self.proj_plans:
                print("[vcycle] " + p.describe().replace("\n", "\n[vcycle] "))
        self.state: Optional[VCycleState] = None
        self._step_fns: Dict[int, Callable] = {}
        self._shardings: Dict[int, Tuple[Any, Any]] = {}
        self._batch_sh = None
        self.n_compiles = 0  # probe: must end up == #levels visited

    def level_shardings(self, level: int) -> Tuple[Any, Any]:
        """(param, opt) NamedSharding trees for ``level``; (None, None) when
        the runner has no mesh.  Cached: layouts are pure functions of the
        level's Spec tree and the mesh."""
        if self.mesh is None:
            return None, None
        got = self._shardings.get(level)
        if got is None:
            from repro.models.api import train_state_shardings

            got = train_state_shardings(self.models[level], self.tc, self.mesh)
            self._shardings[level] = got
        return got

    def ef_shardings(self, level: int):
        """NamedSharding tree for the grad-reduce carried state at ``level``
        (None when the strategy is absent or stateless)."""
        gr = self.grad_reduce
        if gr is None or not gr.stateful or self.mesh is None:
            return None
        psh, _ = self.level_shardings(level)
        return gr.state_shardings(psh, self.mesh)

    def batch_shardings(self):
        """Data-parallel shardings for ``batch_fn``'s pytree (None w/o mesh)."""
        if self.mesh is None:
            return None
        if self._batch_sh is None:
            from repro.distributed import batch_like, batch_shardings

            # batch_like honors a GlobalBatchFn's precomputed .like: the
            # multi-process host->global batch conversion cannot be traced
            # by jax.eval_shape
            self._batch_sh = batch_shardings(batch_like(self.batch_fn),
                                             self.mesh)
        return self._batch_sh

    def step_fn(self, level: int) -> Callable:
        """The compiled train step for ``level`` (built once, then cached).

        With a ``grad_reduce`` strategy the underlying step is the 4-ary
        shard_map'd one (params, opt, ef, batch); the runner wraps it back to
        the loop's 3-ary shape by threading ``self.state.ef`` through, so the
        segment loop, logging and checkpoint cadence stay strategy-agnostic.
        """
        fn = self._step_fns.get(level)
        if fn is None:
            if self.grad_reduce is not None:
                step = make_train_step(self.models[level], self.tc,
                                       grad_reduce=self.grad_reduce,
                                       mesh=self.mesh)
            else:
                step = make_train_step(self.models[level], self.tc)
            # the compiled program, and so the device trace's module, is
            # named by the level: jit_train_step_l0, jit_train_step_l1, ...
            step.__name__ = step.__qualname__ = f"train_step_l{level}"
            if self.mesh is None:
                fn = jax.jit(step, donate_argnums=(0, 1))
            else:
                from jax.sharding import NamedSharding, PartitionSpec

                psh, osh = self.level_shardings(level)
                # metrics are explicitly replicated: the host loss fetch
                # (float()) must work on every process of a multi-process mesh
                rep = NamedSharding(self.mesh, PartitionSpec())
                if self.grad_reduce is not None:
                    efsh = self.ef_shardings(level)
                    if self.drain_flag is not None:
                        fn4 = self.drain_flag.wrap_step(
                            step,
                            in_shardings=(psh, osh, efsh, self.batch_shardings()),
                            out_shardings=(psh, osh, efsh, rep),
                            donate_argnums=(0, 1, 2))
                    else:
                        fn4 = jax.jit(
                            step,
                            in_shardings=(psh, osh, efsh, self.batch_shardings()),
                            out_shardings=(psh, osh, efsh, rep),
                            donate_argnums=(0, 1, 2))

                    def fn(p, o, b, _fn4=fn4):
                        st = self.state
                        p, o, st.ef, m = _fn4(p, o, st.ef, b)
                        return p, o, m
                elif self.drain_flag is not None:
                    fn = self.drain_flag.wrap_step(
                        step,
                        in_shardings=(psh, osh, self.batch_shardings()),
                        out_shardings=(psh, osh, rep))
                else:
                    fn = jax.jit(step,
                                 in_shardings=(psh, osh, self.batch_shardings()),
                                 out_shardings=(psh, osh, rep),
                                 donate_argnums=(0, 1))
            fn = _host_span(fn, level)
            self._step_fns[level] = fn
            self.n_compiles += 1
        return fn

    def init_state(self) -> Tuple[VCycleState, Any]:
        """Fresh (state, params) for an uninterrupted run.  The init is
        deterministic, so on a multi-process mesh every process computes the
        same full value and keeps only its addressable shards."""
        from repro.distributed import put_global_tree

        params = self.models[0].init(jax.random.PRNGKey(self.seed))
        psh, _ = self.level_shardings(0)
        if psh is not None:
            params = put_global_tree(params, psh)
        return VCycleState(), params

    def _init_opt(self, level: int, params):
        """Fresh optimizer state for ``level`` (re-init at transitions, paper
        App. C), laid out on the mesh when there is one."""
        from repro.distributed import put_global_tree

        _, osh = self.level_shardings(level)
        if osh is None:
            return adamw_init(params, self.tc)
        # zeros are built from shapes (host-local), then landed shard-wise --
        # adamw_init on global params would otherwise try a cross-process
        # device_put
        like = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
                            params)
        return put_global_tree(adamw_init(like, self.tc), osh)

    def _init_ef(self, level: int, params):
        """Zero grad-reduce state for ``level`` (None for stateless/absent
        strategies), laid out on the mesh shard-wise like ``_init_opt``."""
        gr = self.grad_reduce
        if gr is None or not gr.stateful:
            return None
        from repro.distributed import put_global_tree

        return put_global_tree(gr.init_state(params), self.ef_shardings(level))

    def _transition(self, state: VCycleState, plan: SegmentPlan, params):
        """Apply the post-segment operator (Alg. 1 lines 3-4 / 7-9); with a
        mesh the projection lands directly on the target level's layout."""
        l = plan.level
        if plan.phase == "down":
            state.params_before[l] = params
            if self.verbose:
                print(f"[vcycle] level {l} init-trained {plan.steps} steps, coalescing")
            return ops.make_coalesce_fn(
                self.specs[l], self.cfgs[l], self.ml,
                out_shardings=self.level_shardings(l + 1)[0],
                plan=self.proj_plans[l])(params)
        if plan.phase == "up":
            if self.verbose:
                print(f"[vcycle] level {l} trained {plan.steps} steps, de-coalescing")
            target_sh = self.level_shardings(l - 1)[0]
            de = ops.make_decoalesce_fn(self.specs[l - 1], self.cfgs[l - 1],
                                        self.ml, out_shardings=target_sh,
                                        plan=self.proj_plans[l - 1])(params)
            # pop, don't read: the stash is consumed here, and dropping it
            # keeps later checkpoints from re-serializing dead full-size trees
            before = state.params_before.pop(l - 1)
            return ops.make_interpolate_fn(
                self.ml.alpha, backend=self.cfgs[l - 1].kernel_backend or None,
                out_shardings=target_sh)(before, de)
        return params

    def run(self, *, state: Optional[VCycleState] = None, params=None,
            opt_state=None, ckpt_cb=None, ckpt_every: int = 0,
            on_step=None) -> VCycleOutput:
        """Run (or resume) the V-cycle to completion.

        Fresh run: call with no arguments.  Resume: pass the restored
        ``state`` + ``params`` (+ ``opt_state`` when mid-segment).  Data
        order is keyed on ``state.global_step``, checkpoints always capture
        the in-segment, pre-transition view, and transitions are
        deterministically replayed from it -- so a resumed run is equivalent
        to an uninterrupted one.  ``on_step(state, params, opt_state,
        stopping, dt)`` fires after every step's bookkeeping (after any
        ``ckpt_cb``) with the step's measured wall time -- it may raise to
        abort the run.
        """
        if state is None:
            state, params = self.init_state()
        elif params is None:
            raise ValueError("resuming from a VCycleState requires params")
        self.state = state
        tc = self.tc
        while state.seg_index < len(self.plan):
            plan = self.plan[state.seg_index]
            state.phase, state.level = plan.phase, plan.level
            fn = self.step_fn(plan.level)
            if opt_state is None:  # re-init at transitions (paper App. C)
                opt_state = self._init_opt(plan.level, params)
            if state.ef is None:  # fresh zeros per level (see VCycleState.ef)
                state.ef = self._init_ef(plan.level, params)
            fps = flops_lib.train_step_flops(
                self.cfgs[plan.level], self.specs[plan.level],
                tc.batch_size, tc.seq_len)

            def _on_step(i, p, o, cum, g, stopping, dt):
                state.cum_flops, state.global_step = cum, g
                state.seg_step = i + 1
                # never checkpoint the stopping step: a restart from it would
                # resume into training the early exit already cut off
                if (ckpt_cb is not None and ckpt_every and not stopping
                        and g % ckpt_every == 0):
                    ckpt_cb(state, p, o)
                if on_step is not None:
                    on_step(state, p, o, stopping, dt)

            params, opt_state, state.cum_flops, state.global_step = _train_loop(
                fn, self.batch_fn, plan.steps, state.seg_step, params,
                opt_state, state.history, state.cum_flops, state.global_step,
                plan.level, fps, tc.log_every,
                self.target_loss if plan.phase == "final" else None,
                on_step=_on_step,
                # honest per-step dt only when someone consumes it; library
                # callers without a hook keep async-dispatch pipelining
                sync_every_step=on_step is not None)
            params = self._transition(state, plan, params)
            state.seg_index += 1
            state.seg_step = 0
            opt_state = None
            # EF residuals are level-shaped; reset across the transition (the
            # next segment re-zeros them -- see the VCycleState.ef rationale)
            state.ef = None
        return VCycleOutput(params=params, history=state.history,
                            configs=self.cfgs, total_flops=state.cum_flops)


def run_vcycle(
    cfg: ModelConfig,
    ml: MultiLevelConfig,
    tc: TrainConfig,
    batch_fn: Callable[[int], Dict[str, jax.Array]],
    *,
    seed: int = 0,
    target_loss: Optional[float] = None,
    final_steps: Optional[int] = None,
    verbose: bool = False,
) -> VCycleOutput:
    """Paper Algorithm 1 (thin wrapper over :class:`VCycleRunner`).

    Step budgets follow the paper: E_a = warmup-sized init segment per level
    before coalescing; E_small = one half of the full cycle for every level
    below the top; the top level then trains until convergence (here: until
    ``target_loss`` or ``final_steps``/``tc.steps``).
    """
    runner = VCycleRunner(cfg, ml, tc, batch_fn, seed=seed,
                          target_loss=target_loss, final_steps=final_steps,
                          verbose=verbose)
    return runner.run()


def run_scratch(
    cfg: ModelConfig,
    tc: TrainConfig,
    batch_fn: Callable[[int], Dict[str, jax.Array]],
    *,
    seed: int = 0,
    steps: Optional[int] = None,
) -> Tuple[Any, History]:
    model = build_model(cfg)
    params, _, hist, _, _ = train_segment(
        model, tc, batch_fn, steps or tc.steps, seed=seed, level=0)
    return params, hist
