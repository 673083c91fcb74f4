"""Fault-tolerance demo, three acts:

1. plain training: checkpoint, simulate preemption, resume on a DIFFERENT
   mesh layout (elastic re-shard on restore);
2. V-cycle training: SIGKILL-style preemption in the middle of the upward
   sweep, then auto-resume at the exact (phase, level, step) -- the pending
   de-coalesce/interpolate transition replays deterministically, with the
   resumed run re-sharded onto a mesh (elastic mid-V-cycle re-shard);
3. multi-process: a real 2-process `jax.distributed` V-cycle run (localhost
   coordinator, ("data","model") mesh spanning both processes, coordinated
   per-process checkpoint shards), preempted by a SIGTERM to ONE process --
   the drain flag all-reduces so both save the same step and exit 0 -- then
   resumed by a SINGLE process (checkpoints are process-count-elastic).

For the real CLI versions: `--mesh DxM` + SIGKILL/SIGTERM drills live in
scripts/smoke_resume.sh and tests/test_system.py / test_multiprocess.py.

A CPU drill: act 3 starts two trainers while this process holds its own
backend, and a chip belongs to one process at a time, so every child runs
with ``JAX_PLATFORMS=cpu`` (gloo collectives between the two processes).

    PYTHONPATH=src JAX_PLATFORMS=cpu python examples/elastic_restart.py
"""
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.config import MultiLevelConfig, TrainConfig
from repro.configs import get_config
from repro.core.vcycle import VCycleRunner
from repro.launch.mesh import make_mesh
from repro.launch.train import make_batch_fn, make_vcycle_save_cb, train_vcycle_ckpt
from repro.models.api import build_model, init_train_state, make_train_step

CKPT = "/tmp/elastic_demo_ckpt"
CKPT_VCYCLE = "/tmp/elastic_demo_vcycle_ckpt"
CKPT_MP = "/tmp/elastic_demo_mp_ckpt"


class Preempted(RuntimeError):
    """Stand-in for a SIGKILL: aborts the process mid-training."""


def main():
    shutil.rmtree(CKPT, ignore_errors=True)
    cfg = get_config("tinyllama-1.1b", smoke=True)
    tc = TrainConfig(steps=12, warmup_steps=1, batch_size=4, seq_len=32, log_every=2)
    model = build_model(cfg)
    batch_fn = make_batch_fn(cfg, tc)
    step = jax.jit(make_train_step(model, tc))
    cm = CheckpointManager(CKPT)

    params, opt = init_train_state(model, tc, jax.random.PRNGKey(0))
    print("== phase 1: train 6 steps on 'mesh A' then checkpoint ==")
    for i in range(6):
        params, opt, m = step(params, opt, batch_fn(i))
    cm.save(6, {"params": params, "opt": opt}, meta={"step": 6})
    print(f"checkpointed at step 6 (loss {float(m['loss']):.4f})")

    print("== simulated preemption: process state dropped ==")
    del params, opt

    print("== phase 2: resume onto a different mesh layout ==")
    # container has 1 CPU device; the mechanism is identical for any topology:
    # pass target NamedShardings and restore() re-shards with device_put.
    mesh_b = make_mesh((1, 1), ("data", "model"))
    p0, o0 = init_train_state(model, tc, jax.random.PRNGKey(0))
    sh = {
        "params": jax.tree.map(lambda _: NamedSharding(mesh_b, P()), p0),
        "opt": jax.tree.map(lambda _: NamedSharding(mesh_b, P()), o0),
    }
    restored, meta = cm.restore({"params": p0, "opt": o0}, shardings=sh)
    params, opt = restored["params"], restored["opt"]
    print(f"resumed from step {meta['step']} onto mesh {dict(mesh_b.shape)}")
    for i in range(meta["step"], tc.steps):
        params, opt, m = step(params, opt, batch_fn(i))
    print(f"finished at step {tc.steps} (loss {float(m['loss']):.4f}) -- "
          "deterministic data sharding made the resumed stream identical")


def main_vcycle():
    shutil.rmtree(CKPT_VCYCLE, ignore_errors=True)
    cfg = get_config("tinyllama-1.1b", smoke=True)
    tc = TrainConfig(steps=12, warmup_steps=1, batch_size=2, seq_len=16, log_every=4)
    ml = MultiLevelConfig(n_levels=2, alpha=0.25, e_a_frac=0.25, e_small_frac=0.5)
    cm = CheckpointManager(CKPT_VCYCLE)

    print("== phase 1: V-cycle, checkpoint every 2 steps, die mid-upward-sweep ==")
    runner = VCycleRunner(cfg, ml, tc, make_batch_fn(cfg, tc), seed=0, verbose=True)
    save_cb = make_vcycle_save_cb(cm, schedule=runner.plan)

    def killing_cb(state, params, opt_state):
        save_cb(state, params, opt_state)
        if state.phase == "up":
            raise Preempted(f"preempted at global step {state.global_step}")

    try:
        runner.run(ckpt_cb=killing_cb, ckpt_every=2)
    except Preempted as e:
        cm.wait()  # a real SIGKILL relies on atomic publish instead
        print(f"== {e}; restarting fresh ==")

    print("== phase 2: auto-resume picks up inside the upward sweep, and "
          "re-shards onto a mesh while doing it ==")
    # elastic mid-V-cycle re-shard: the checkpoint was written UNSHARDED, but
    # the resumed run is mesh-parallel -- params, opt and the stashed
    # params_before_* trees all land on the mesh layouts (the container has 1
    # CPU device, so 1x1; the mechanism is identical for any DxM -- the
    # launcher's `--mesh 2x1` does exactly this after a `--mesh 1x2` save)
    mesh = make_mesh((1, 1), ("data", "model"))
    out = train_vcycle_ckpt(cfg, ml, tc, ckpt=cm, ckpt_every=4, mesh=mesh)
    print(f"finished: final loss {out.history.loss[-1]:.4f}, "
          f"total FLOPs {out.total_flops:.3e}")


def main_multiprocess():
    shutil.rmtree(CKPT_MP, ignore_errors=True)
    print("== phase 1: 2-process V-cycle (localhost coordinator), SIGTERM "
          "delivered to process 1 only ==")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "tinyllama-1.1b", "--smoke", "--vcycle", "--levels", "2",
            "--steps", "40", "--batch", "4", "--seq", "16", "--f32",
            "--ckpt-dir", CKPT_MP, "--ckpt-every", "1000"]
    mp = ["--mesh", "2x1", "--coordinator", f"127.0.0.1:{port}",
          "--num-processes", "2"]
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    logs = [f"{CKPT_MP}.rank{i}.log" for i in (0, 1)]
    os.makedirs(CKPT_MP, exist_ok=True)
    procs = []
    for i in (0, 1):
        with open(logs[i], "w") as lf:
            procs.append(subprocess.Popen(
                args + mp + ["--process-id", str(i)], env=env, stdout=lf,
                stderr=subprocess.STDOUT))
    # wait until training is demonstrably stepping (past the first segment),
    # so the SIGTERM lands mid-cycle with the preemption handler installed
    try:
        deadline = time.time() + 240
        while time.time() < deadline and all(p.poll() is None for p in procs):
            if "coalescing" in open(logs[0]).read():
                break
            time.sleep(0.2)
        procs[1].send_signal(signal.SIGTERM)  # ONE process gets the notice...
        for p in procs:
            p.wait(timeout=240)
    finally:
        for p in procs:  # a wedged drain must not leave orphans training
            if p.poll() is None:
                p.kill()
                p.wait()
    # ...and the all-reduced drain flag makes BOTH save the same step + exit 0
    for i, p in enumerate(procs):
        out = open(logs[i]).read()
        drain = [l for l in out.splitlines() if "[preempt]" in l]
        print(f"process {i}: exit {p.returncode}; " +
              (drain[-1] if drain else "(no drain line)"))

    print("== phase 2: the 2-process checkpoint resumes under ONE process ==")
    out = subprocess.run(args, env=env, capture_output=True, text=True,
                         timeout=480).stdout
    for l in out.splitlines():
        if "resumed at phase=" in l or "total training FLOPs" in l:
            print(l)


if __name__ == "__main__":
    main()
    main_vcycle()
    main_multiprocess()
