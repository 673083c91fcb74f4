"""Production-shaped training driver.

Runs real training (proxy/smoke scale on this CPU container; the same code
path drives a sharded mesh via ``--mesh DxM``), with:

* V-cycle multi-level schedule (``--vcycle``) or plain from-scratch,
* mesh parallelism: ``--mesh 2x4`` builds a ("data", "model") mesh (host CPU
  devices are forced when needed, so the flag works on a laptop), enters the
  sharding-rules context, and jits every train step -- per V-cycle level --
  with explicit ``in_shardings``/``out_shardings`` derived from the level's
  Spec tree, donation included; level transitions (coalesce /
  de-coalesce+interpolate) project sharded-in, sharded-out onto the target
  level's layout,
* fault tolerance: atomic async checkpointing every ``--ckpt-every`` steps
  with auto-resume; V-cycle runs save and restore the full mid-cycle state
  (phase, level, step-within-segment, FLOPs history, interpolation stashes),
  so a SIGKILL at any point -- including mid-upward-sweep -- resumes
  equivalently to an uninterrupted run (scripts/smoke_resume.sh drills this),
* elastic re-shard on restore: checkpoints store logical (unsharded) arrays,
  so a run saved under ``--mesh 1x2`` resumes under ``--mesh 2x1`` (or no
  mesh at all) -- including mid-upward-sweep with the ``params_before_*``
  stashes re-sharded (tests/test_distributed.py pins the equivalence),
* multi-process (multi-host) training: ``--coordinator ADDR
  --num-processes N --process-id I`` runs ``jax.distributed.initialize``
  (CPU-portable: gloo collectives + forced host devices, so CI drills the
  same path as a real slice) and the ``--mesh`` then SPANS processes.
  Process roles are explicit -- logging, the watchdog and the checkpoint
  manifest publish live on process 0 only; every process feeds its own data
  shard and writes only its addressable checkpoint shards (coordinated save
  with a barrier before publish, see ``repro.checkpoint``); checkpoints stay
  logical, so a run saved by 2 processes resumes under 1 (and vice versa),
* preemption awareness: SIGTERM on ANY ONE process propagates through an
  all-reduced drain flag, so every process runs the SAME final blocking
  checkpoint at one agreed step boundary and exits 0, instead of hoping the
  cadence saved recently (scripts/smoke_resume.sh acts 2+3 drill this),
* deterministic host-sharded synthetic data keyed on
  ``repro.distributed.data_shard_index`` (any host can regenerate any
  shard -> straggler/elastic-safe; a data-parallel process's shard is its
  slice of the process-count-invariant global batch, so runs agree across
  process counts),
* a step-time watchdog that flags stragglers (steps slower than ``factor`` x
  the median of PRIOR step times are logged) on both drivers.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
      --steps 50 --ckpt-dir /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
      --vcycle --mesh 1x2 --steps 20 --ckpt-dir /tmp/ck
  # multi-process (run one per host / terminal; same args except --process-id)
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
      --vcycle --mesh 2x1 --steps 20 --ckpt-dir /tmp/ck \
      --coordinator 127.0.0.1:9876 --num-processes 2 --process-id 0
"""
from __future__ import annotations

import argparse
import contextlib
import json
import signal
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.config import SHAPES, MultiLevelConfig, TrainConfig
from repro.configs import get_config
from repro.core import flops as flops_lib
from repro.core import operators as ops
from repro.core.vcycle import History, VCycleOutput, VCycleRunner, VCycleState
from repro.data import MarkovLM, lm_batch, masked_lm_batch, vision_batch
from repro.distributed import (any_process_flag, as_global_batch_fn,
                               batch_like, batch_shardings, data_shard_index,
                               is_primary, mesh_ctx, put_global_tree)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import init_distributed, make_cli_mesh, parse_mesh_arg
from repro.models.api import (build_model, init_train_state, make_train_step,
                              train_state_shardings, zero_train_state)
from repro.optim import adamw_init


def make_batch_fn(cfg, tc: TrainConfig, shard: int = 0):
    if cfg.family == "vit":
        from repro.models.vit import n_patches, patch_dim

        return lambda step: vision_batch(tc.seed, step, tc.batch_size, n_patches(cfg),
                                         patch_dim(cfg), cfg.n_classes, shard)
    chain = MarkovLM(cfg.vocab_size)
    if cfg.family == "encoder":
        mask_id = cfg.vocab_size - 1
        return lambda step: masked_lm_batch(chain, tc.seed, step, tc.batch_size,
                                            tc.seq_len, mask_id, shard=shard)

    def fn(step):
        b = lm_batch(chain, tc.seed, step, tc.batch_size, tc.seq_len, shard)
        if cfg.family == "vlm":
            b["img_embeds"] = jnp.ones(
                (tc.batch_size, cfg.n_image_tokens, cfg.vision_dim or cfg.d_model),
                cfg.compute_dtype)
        if cfg.family == "audio":
            b["enc_frames"] = jnp.ones((tc.batch_size, cfg.encoder_seq, cfg.d_model),
                                       cfg.compute_dtype)
        return b

    return fn


def make_driver_batch_fn(cfg, tc: TrainConfig, mesh):
    """The launcher's per-process batch stream.

    Single-process: the canonical shard named by ``data_shard_index`` (0).
    Multi-process: every process regenerates the SAME canonical global batch
    (``data/synthetic`` batches are pure functions of (seed, step, shard), so
    any host can) and materializes only the rows its data-axis coordinate --
    ``data_shard_index(mesh)`` -- addresses.  The global data stream is
    therefore invariant to the process count, which is what makes the
    2-process-vs-1-process equivalence and cross-process-count resume
    well-posed (tests/test_multiprocess.py pins both).
    """
    if jax.process_count() > 1:
        return as_global_batch_fn(make_batch_fn(cfg, tc, shard=0), mesh)
    return make_batch_fn(cfg, tc, shard=data_shard_index(mesh))


class Watchdog:
    """Step-time straggler detector (multi-host analogue: per-host heartbeat)."""

    def __init__(self, factor: float = 3.0):
        self.times: list = []
        self.factor = factor
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        # median over PRIOR samples only: appending first let the straggler
        # dilute its own baseline (a spike entering the window shifts the
        # median up and can mask itself right at the flagging threshold).
        # Only the trailing window is ever read, so don't grow unbounded
        # over multi-day runs.
        prior = self.times[-50:]
        self.times = prior + [dt]
        if len(prior) >= 10:
            med = float(np.median(prior))
            if dt > self.factor * med:
                self.flagged += 1
                print(f"[watchdog] slow step: {dt*1e3:.0f}ms vs median {med*1e3:.0f}ms")
                return True
        return False


class PreemptionGuard:
    """SIGTERM-aware preemption notice, coordinated across processes.

    The handler only sets a flag (async-signal-safe); the training loops poll
    :meth:`should_stop` exactly once per step and run ONE final *blocking*
    checkpoint before exiting 0 -- preempted pods save at the notice instead
    of waiting for the ``--ckpt-every`` cadence.

    In multi-process runs ``should_stop`` reduces the flag across processes,
    so a SIGTERM delivered to ANY ONE process drains the whole job: every
    process sees the notice at the same step boundary, runs the same
    coordinated final save, and exits 0 together.  Because the poll is a
    collective, the drivers call it unconditionally each step on every
    process.

    The reduction itself is FUSED into the compiled train step when a
    ``distributed.FusedDrainFlag`` is attached (both drivers do, on
    multi-process meshes): the flag enters the step as one int32 element per
    device and comes back as a replicated ``metrics["drain"]`` scalar, so the
    cross-process OR rides the step's existing collective schedule instead of
    a dedicated per-step ``process_allgather``.  Without one attached,
    ``should_stop`` falls back to the explicit allgather.
    """

    def __init__(self):
        self.triggered = False
        self.fused = None  # a FusedDrainFlag once attach() is called

    def attach(self, drain_flag):
        """Bind a ``FusedDrainFlag``: ``should_stop`` reads the last fused
        step's replicated drain scalar instead of all-gathering."""
        self.fused = drain_flag
        drain_flag.guard = self
        return drain_flag

    def install(self, signals=(signal.SIGTERM,)) -> "PreemptionGuard":
        for s in signals:
            try:
                signal.signal(s, self._handler)
            except ValueError:  # not the main thread (e.g. embedded in a test)
                break
        return self

    def _handler(self, signum, frame):
        self.triggered = True
        print(f"[preempt] caught signal {signum}; will checkpoint and exit at "
              "the next step boundary", flush=True)

    def should_stop(self) -> bool:
        """True when ANY process holds a preemption notice (collective in
        multi-process runs -- call symmetrically, once per step)."""
        if self.fused is not None:
            # the OR already ran inside the step; local flag covers the
            # pre-first-step window
            return self.fused.last() or (jax.process_count() == 1
                                         and self.triggered)
        return any_process_flag(self.triggered)


def _report_reduce_probe(tc: TrainConfig, verbose: bool) -> None:
    """Assert the compressed path actually ran (trace-time call probe), not
    just that the flag was set -- and say so, greppable, for the CLI drills."""
    if tc.grad_compression != "int8_ef":
        return
    from repro.distributed.compression import ef_psum_calls

    n = ef_psum_calls()
    if n <= 0:
        raise RuntimeError(
            "--grad-compression int8_ef was requested but ef_int8_psum was "
            "never traced into a compiled step")
    if verbose:
        print(f"[reduce] probe: ef_int8_psum traced into {n} compiled step(s)",
              flush=True)


def train_plain(cfg, tc: TrainConfig, *, ckpt: Optional[CheckpointManager],
                ckpt_every: int, verbose: bool = True, mesh=None,
                preempt: Optional[PreemptionGuard] = None):
    model = build_model(cfg)
    batch_fn = make_driver_batch_fn(cfg, tc, mesh)
    params, opt = init_train_state(model, tc, jax.random.PRNGKey(tc.seed))
    psh = osh = bsh = efsh = None
    gr = ef = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.distributed import make_grad_reduce

        gr = make_grad_reduce(tc.grad_compression, mesh)
        psh, osh = train_state_shardings(model, tc, mesh)
        if gr is not None and gr.stateful:
            efsh = gr.state_shardings(psh, mesh)
        # put_global_tree: plain device_put when the mesh is local, shard-wise
        # landing when it spans processes (init is deterministic, every
        # process holds the full value)
        params = put_global_tree(params, psh)
        opt = put_global_tree(opt, osh)
        if efsh is not None:
            ef = put_global_tree(gr.init_state(params), efsh)
        bsh = batch_shardings(batch_like(batch_fn), mesh)
        metrics_sh = NamedSharding(mesh, PartitionSpec())  # host-readable everywhere
    start = 0
    if ckpt is not None:
        # elastic restore: the checkpoint holds logical arrays, so target
        # shardings may describe a different mesh (or process count) than the
        # one that saved
        has_ef = bool((ckpt.latest() or {}).get("meta", {}).get("has_ef"))
        if has_ef and efsh is None:
            raise ValueError(
                "checkpoint carries grad-reduction (EF) state; resume with "
                "--grad-compression int8_ef on the same mesh shape")
        like = {"params": params, "opt": opt}
        sh = None if mesh is None else {"params": psh, "opt": osh}
        if has_ef:
            like["ef"], sh["ef"] = ef, efsh
        restored, meta = ckpt.restore(like, shardings=sh)
        if restored is not None:
            params, opt = restored["params"], restored["opt"]
            if has_ef:
                ef = restored["ef"]
            start = int(meta.get("step", 0))
            if verbose:
                print(f"[train] resumed from step {start}")
    if mesh is None:
        step_fn = jax.jit(make_train_step(model, tc), donate_argnums=(0, 1))
    else:
        drain = None
        if preempt is not None and jax.process_count() > 1:
            from repro.distributed import FusedDrainFlag

            drain = preempt.attach(FusedDrainFlag(mesh, guard=preempt))
        base_step = make_train_step(model, tc, grad_reduce=gr,
                                    mesh=mesh if gr is not None else None)
        if gr is not None:
            # 4-ary (params, opt, ef, batch) step with the reduction strategy
            # injected; wrapped back to the loop's 3-ary shape below
            if drain is not None:
                fn4 = drain.wrap_step(
                    base_step,
                    in_shardings=(psh, osh, efsh, bsh),
                    out_shardings=(psh, osh, efsh, metrics_sh),
                    donate_argnums=(0, 1, 2))
            else:
                fn4 = jax.jit(base_step,
                              in_shardings=(psh, osh, efsh, bsh),
                              out_shardings=(psh, osh, efsh, metrics_sh),
                              donate_argnums=(0, 1, 2))

            def step_fn(p, o, b):
                nonlocal ef
                p, o, ef, m = fn4(p, o, ef, b)
                return p, o, m
        elif drain is not None:
            step_fn = drain.wrap_step(base_step,
                                      in_shardings=(psh, osh, bsh),
                                      out_shardings=(psh, osh, metrics_sh))
        else:
            step_fn = jax.jit(base_step,
                              in_shardings=(psh, osh, bsh),
                              out_shardings=(psh, osh, metrics_sh),
                              donate_argnums=(0, 1))
    def _snapshot(step):
        payload = {"params": params, "opt": opt}
        if ef is not None:
            payload["ef"] = ef  # EF residuals resume with the run (unbiasedness)
        return payload, {"step": step, "has_ef": ef is not None}

    # the watchdog is a process-0 role (single-process runs are process 0)
    wd = Watchdog() if is_primary() else None
    for i in range(start, tc.steps):
        t0 = time.time()
        params, opt, metrics = step_fn(params, opt, batch_fn(i))
        # heartbeat EVERY step (a straggler on a non-log step must be seen);
        # block on device completion only -- the host metric fetch stays on
        # log steps
        jax.block_until_ready(metrics["loss"])
        if wd is not None:
            wd.observe(time.time() - t0)
        # coordinated drain: polled unconditionally once per step on every
        # process (it is a collective), so a SIGTERM on any ONE process makes
        # ALL processes save the same step and exit 0 together
        if preempt is not None and preempt.should_stop():
            if ckpt is not None:
                payload, meta = _snapshot(i + 1)
                ckpt.save(i + 1, payload, meta=meta, blocking=True)
                print(f"[preempt] SIGTERM: final checkpoint at step {i + 1}; "
                      "exiting", flush=True)
            raise SystemExit(0)
        if i % tc.log_every == 0:
            loss = float(metrics["loss"])
            if verbose:
                print(f"[train] step {i} loss {loss:.4f} lr {float(metrics['lr']):.2e}")
        if ckpt is not None and ckpt_every and i and i % ckpt_every == 0:
            payload, meta = _snapshot(i + 1)
            ckpt.save(i, payload, meta=meta, blocking=False)
    if ckpt is not None:
        payload, meta = _snapshot(tc.steps)
        ckpt.save(tc.steps, payload, meta=meta)
    _report_reduce_probe(tc, verbose)
    return params


def _schedule_meta(plan) -> list:
    """JSON form of a segment schedule, stored with every mid-cycle
    checkpoint so restore can refuse a mismatched (phase, level, step)
    addressing instead of silently training the wrong schedule."""
    return [[p.phase, p.level, p.steps] for p in plan]


def make_vcycle_save_cb(ckpt: CheckpointManager, schedule=None):
    """A ``VCycleRunner`` checkpoint hook writing the full resumable state.

    Array payload: the in-segment ``params`` + ``opt`` plus every stashed
    ``params_before_<level>`` tree (needed by Interpolation on the upward
    sweep).  Manifest metadata: (phase, level, seg_index, seg_step,
    global_step, cum_flops, stashed_levels, history) plus the segment
    ``schedule`` (pass the runner's ``plan``) that anchors those indices.
    Saves are async -- ``CheckpointManager`` snapshots to host before the
    training loop mutates anything.
    """
    sched = _schedule_meta(schedule) if schedule is not None else None

    def save_cb(state: VCycleState, params, opt_state, blocking: bool = False) -> None:
        stashed = sorted(state.params_before)
        payload = {"params": params, "opt": opt_state,
                   **{f"params_before_{l}": state.params_before[l] for l in stashed}}
        if state.ef is not None:
            # carried EF residuals: resuming without them would re-bias the
            # first post-restore steps (the unbiasedness guarantee is exactly
            # that transmitted + carried == true gradient over time)
            payload["ef"] = state.ef
        meta = {
            "step": state.global_step, "phase": state.phase, "level": state.level,
            "seg_index": state.seg_index, "seg_step": state.seg_step,
            "global_step": state.global_step, "cum_flops": state.cum_flops,
            "stashed_levels": stashed, "history": state.history.to_dict(),
            "has_ef": state.ef is not None}
        if sched is not None:
            meta["schedule"] = sched
        ckpt.save(state.global_step, payload, meta=meta, blocking=blocking)

    return save_cb


def restore_vcycle_state(ckpt: CheckpointManager, runner: VCycleRunner,
                         tc: TrainConfig):
    """(state, params, opt_state) from the newest mid-cycle checkpoint.

    Inverse of :func:`make_vcycle_save_cb`: like-trees come from
    ``zero_train_state`` of the checkpointed level's model, so no RNG or
    training work happens before the arrays land.  When ``runner`` carries a
    mesh, every restored tree -- the in-segment params/opt AND each
    ``params_before_<level>`` stash -- is device_put straight onto that
    runner's per-level layouts, so a checkpoint written under mesh A resumes
    under mesh B (elastic mid-V-cycle re-shard).  Raises ``ValueError`` if
    the checkpoint's segment schedule (or position) does not fit ``runner``'s
    -- resuming a checkpoint under different ``--steps``/``--levels`` would
    otherwise silently train the wrong schedule.
    """
    m = ckpt.latest()
    meta = m["meta"]
    current = _schedule_meta(runner.plan)
    saved = meta.get("schedule")
    if saved is not None and [list(s) for s in saved] != current:
        raise ValueError(
            f"checkpoint was written under a different V-cycle schedule "
            f"({saved} vs current {current}); restart with the original "
            f"--steps/--levels or use a fresh --ckpt-dir")
    seg_index = int(meta["seg_index"])
    if (seg_index >= len(runner.plan)
            or int(meta["seg_step"]) > runner.plan[seg_index].steps):
        raise ValueError(
            f"checkpoint position (seg_index={seg_index}, "
            f"seg_step={meta['seg_step']}) lies outside the current schedule "
            f"{current}; restart with the original --steps/--levels")
    level = int(meta["level"])
    has_ef = bool(meta.get("has_ef"))
    gr = runner.grad_reduce
    if has_ef and (gr is None or not gr.stateful):
        raise ValueError(
            "checkpoint carries grad-reduction (EF) state; resume with "
            "--grad-compression int8_ef on the same mesh shape")
    like_p, like_o = zero_train_state(runner.models[level], tc)
    like = {"params": like_p, "opt": like_o}
    if has_ef:
        like["ef"] = zero_train_state(runner.models[level], tc,
                                      grad_reduce=gr)[2]
    stashed = [int(l) for l in meta.get("stashed_levels", [])]
    for l in stashed:
        like[f"params_before_{l}"] = zero_train_state(runner.models[l], tc)[0]
    shardings = None
    if runner.mesh is not None:
        psh, osh = runner.level_shardings(level)
        shardings = {"params": psh, "opt": osh}
        if has_ef:
            shardings["ef"] = runner.ef_shardings(level)
        for l in stashed:
            shardings[f"params_before_{l}"] = runner.level_shardings(l)[0]
    restored, meta = ckpt.restore(like, shardings=shardings)
    state = VCycleState(
        phase=meta["phase"], level=level,
        seg_index=int(meta["seg_index"]), seg_step=int(meta["seg_step"]),
        global_step=int(meta["global_step"]), cum_flops=float(meta["cum_flops"]),
        history=History(**{k: list(v) for k, v in meta["history"].items()}),
        params_before={l: restored[f"params_before_{l}"] for l in stashed},
        ef=restored.get("ef"))
    return state, restored["params"], restored["opt"]


def train_vcycle_ckpt(cfg, ml: MultiLevelConfig, tc: TrainConfig, *,
                      ckpt: Optional[CheckpointManager], ckpt_every: int,
                      verbose: bool = True, mesh=None,
                      preempt: Optional[PreemptionGuard] = None):
    """V-cycle with real (phase, level, step) checkpoint/resume.

    Every ``ckpt_every`` global steps the runner's hook saves
    ``{params, opt, params_before_*}`` + V-cycle state metadata (async,
    atomic).  On restart this function restores the newest checkpoint and
    re-enters ``VCycleRunner.run`` at the exact (phase, level, seg_step) --
    including mid-upward-sweep, where the pending de-coalesce/interpolate
    transition is replayed deterministically from the in-segment params.
    Deterministic ``batch_fn(global_step)`` data order makes the resumed run
    equivalent to an uninterrupted one (tests/test_resume.py asserts
    allclose on final params and History).  A terminal "phase=done"
    checkpoint makes re-invocation after completion a no-op.

    ``mesh`` shards the whole cycle (per-level explicit-sharding train steps
    and sharded level transitions); because checkpoints store logical arrays,
    the mesh -- and the PROCESS COUNT -- at restore time may differ from the
    one that saved (a 2-process save resumes under 1 process and vice versa).
    The runner's per-step hook carries the straggler watchdog heartbeat and
    the coordinated preemption poll: a SIGTERM on any one process drains ALL
    processes through one final BLOCKING checkpoint at the same global step,
    followed by a clean exit 0.
    """
    batch_fn = make_driver_batch_fn(cfg, tc, mesh)
    drain = None
    if mesh is not None and preempt is not None and jax.process_count() > 1:
        from repro.distributed import FusedDrainFlag

        drain = preempt.attach(FusedDrainFlag(mesh, guard=preempt))
    runner = VCycleRunner(cfg, ml, tc, batch_fn, seed=tc.seed, verbose=verbose,
                          mesh=mesh, drain_flag=drain)
    state = params = opt = None
    if ckpt is not None:
        m = ckpt.latest()
        meta = (m or {}).get("meta", {})
        if "phase" in meta:
            if meta["phase"] == "done":
                like_p, _ = zero_train_state(runner.models[0], tc)
                restored, _ = ckpt.restore(
                    {"params": like_p},
                    shardings=(None if mesh is None
                               else {"params": runner.level_shardings(0)[0]}))
                if verbose:
                    print("[vcycle] checkpoint already complete; returning saved params")
                return VCycleOutput(
                    params=restored["params"],
                    history=History(**{k: list(v) for k, v in
                                       meta.get("history", {}).items()}),
                    configs=runner.cfgs,
                    total_flops=float(meta.get("cum_flops", 0.0)))
            state, params, opt = restore_vcycle_state(ckpt, runner, tc)
            if verbose:
                print(f"[vcycle] resumed at phase={state.phase} level={state.level} "
                      f"seg_step={state.seg_step} global_step={state.global_step}")
    save_cb = (make_vcycle_save_cb(ckpt, schedule=runner.plan)
               if ckpt is not None else None)
    # one watchdog PER LEVEL: a half-width level's steps are ~8x cheaper, so a
    # shared median would flag every full-size step of the upward sweep; the
    # watchdog is a process-0 role (single-process runs are process 0)
    wds: Optional[Dict[int, Watchdog]] = {} if is_primary() else None

    def on_step(st: VCycleState, p, o, stopping: bool, dt: float) -> None:
        # dt is the runner-measured, device-blocked step time, so checkpoint
        # snapshots and level transitions never read as stragglers; each
        # segment's first step is skipped too -- it may carry the level's
        # one-time jit compile inside the timed step call
        if wds is not None and st.seg_step > 1:
            wds.setdefault(st.level, Watchdog()).observe(dt)
        # coordinated drain: the poll is a collective, so it runs
        # unconditionally once per step on every process; a stopping step is
        # never persisted (see VCycleRunner.run), so a preemption on it just
        # lets the normal completion path finish
        drain = preempt is not None and preempt.should_stop()
        if drain and not stopping:
            if save_cb is not None:
                save_cb(st, p, o, blocking=True)
                print(f"[preempt] SIGTERM: blocking V-cycle checkpoint at "
                      f"global_step {st.global_step}; exiting", flush=True)
            raise SystemExit(0)

    out = runner.run(state=state, params=params, opt_state=opt,
                     ckpt_cb=save_cb, ckpt_every=ckpt_every, on_step=on_step)
    if ckpt is not None:
        gs = runner.state.global_step
        ckpt.save(gs, {"params": out.params},
                  meta={"step": gs, "phase": "done", "level": 0,
                        "global_step": gs, "cum_flops": out.total_flops,
                        "history": out.history.to_dict()})
    _report_reduce_probe(tc, verbose)
    if verbose:
        print(f"[vcycle] total training FLOPs: {out.total_flops:.3e}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true", help="reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--vcycle", action="store_true")
    ap.add_argument("--levels", type=int, default=2)
    ap.add_argument("--alpha", type=float, default=0.25)
    ap.add_argument("--mesh", default="",
                    help="DxM ('data','model') mesh, e.g. 2x4, or PxDxM "
                         "('pod','data','model') with a leading DCN axis, e.g. "
                         "2x1x1; host CPU devices are forced when the platform "
                         "has fewer (smoke/tests); with --num-processes > 1 "
                         "the mesh spans processes")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "dense", "int8_ef"],
                    help="gradient-reduction strategy (distributed/reduce.py): "
                         "'none' keeps pjit's implicit reduction; 'dense' runs "
                         "the explicit shard_map'd full-precision reduction; "
                         "'int8_ef' reduces dense within ICI and int8+error-"
                         "feedback across the DCN ('pod') axis. Needs --mesh")
    ap.add_argument("--coordinator", default="127.0.0.1:9876",
                    help="jax.distributed coordinator host:port (multi-process "
                         "runs; process 0's address)")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="total process count for jax.distributed; every "
                         "process runs this same command with its own "
                         "--process-id and a shared --ckpt-dir")
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--f32", action="store_true",
                    help="force float32 compute (tight cross-mesh resume "
                         "equivalence; default keeps the config's dtype)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-local-dir", default="",
                    help="per-host LOCAL checkpoint dir for clusters without "
                         "a shared filesystem: each process passes its OWN "
                         "path; chunks stay on the local disk, manifests and "
                         "missing objects travel over the coordination "
                         "service (overrides --ckpt-dir)")
    ap.add_argument("--ckpt-dedup", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="content-addressed v3 checkpoint layout: unchanged "
                         "leaves cost no I/O across consecutive saves "
                         "(--no-ckpt-dedup writes the v2 whole-file layout)")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--describe-plans", action="store_true",
                    help="print each V-cycle level transition's ProjectionPlan "
                         "(family hooks, coalesced/protected axes, carried "
                         "fields) and exit without training")
    args = ap.parse_args()
    enable_compile_cache()

    # multi-process bring-up, then the mesh, must both happen before ANY
    # device-touching jax call: distributed init selects the gloo CPU
    # collectives and both may need to force the host device count, which
    # only works pre-backend-init
    if args.grad_compression != "none" and not args.mesh:
        ap.error("--grad-compression needs --mesh (the reduction axes live "
                 "on the mesh; use e.g. --mesh 2x1 or --mesh 2x1x1)")
    if args.num_processes > 1:
        if not args.mesh:
            args.mesh = f"{args.num_processes}x1"  # pure data-parallel default
        dims = parse_mesh_arg(args.mesh)
        total = 1
        for d in dims:
            total *= d
        init_distributed(args.coordinator, args.num_processes, args.process_id,
                         local_devices=total // args.num_processes)
    mesh = (make_cli_mesh(args.mesh, num_processes=args.num_processes)
            if args.mesh else None)
    primary = is_primary()
    if args.num_processes > 1 and args.ckpt_dir:
        print(f"[launch] process {jax.process_index()}/{jax.process_count()} "
              f"up; data shard {data_shard_index(mesh)}", flush=True)

    try:
        cfg = get_config(args.arch, smoke=args.smoke)
    except KeyError:
        from repro.configs import paper_models

        cfg = {"gpt-proxy": paper_models.gpt_proxy(), "bert-proxy": paper_models.bert_proxy(),
               "deit-proxy": paper_models.deit_proxy()}[args.arch]
    if args.f32:
        cfg = cfg.replace(compute_dtype=jnp.float32)
    if args.describe_plans:
        from repro.core import plans as plans_lib

        ml = MultiLevelConfig(n_levels=args.levels, alpha=args.alpha)
        c = cfg
        for _ in range(ml.n_levels - 1):
            p = plans_lib.build_plan(c, ml)
            print(p.describe())
            c = p.small_cfg
        return
    tc = TrainConfig(steps=args.steps, warmup_steps=max(args.steps // 20, 1),
                     peak_lr=args.lr, batch_size=args.batch, seq_len=args.seq,
                     seed=args.seed, grad_compression=args.grad_compression)
    if args.grad_compression != "none" and primary:
        print(f"[reduce] grad-compression={args.grad_compression} over mesh "
              f"{args.mesh} (axes {mesh.axis_names})", flush=True)
    if args.ckpt_local_dir:
        if not args.ckpt_dedup:
            # the no-shared-FS protocol exchanges digests, which only exist
            # in the content-addressed layout -- don't silently ignore the
            # explicitly requested v2 layout
            ap.error("--no-ckpt-dedup is incompatible with --ckpt-local-dir "
                     "(the per-host store is content-addressed by design)")
        ckpt = CheckpointManager(args.ckpt_local_dir, local=True)
    elif args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, dedup=args.ckpt_dedup)
    else:
        ckpt = None
    preempt = PreemptionGuard().install() if ckpt is not None else None
    with (mesh_ctx(mesh) if mesh is not None else contextlib.nullcontext()):
        if args.vcycle:
            ml = MultiLevelConfig(n_levels=args.levels, alpha=args.alpha)
            train_vcycle_ckpt(cfg, ml, tc, ckpt=ckpt, ckpt_every=args.ckpt_every,
                              mesh=mesh, preempt=preempt, verbose=primary)
        else:
            train_plain(cfg, tc, ckpt=ckpt, ckpt_every=args.ckpt_every,
                        mesh=mesh, preempt=preempt, verbose=primary)


if __name__ == "__main__":
    main()
