"""End-to-end behaviour tests for the paper's system: the V-cycle actually
saves compute on a learnable task; the paper's key ablation directions hold
(Appendix D/F/G at proxy scale); serving works; the launcher resumes (plain
and mid-V-cycle, including after SIGKILL); the watchdog sees every step."""
import signal
import subprocess
import sys
import os
import time

import jax
import numpy as np
import pytest

from helpers import fast_tc, tiny_dense
from repro.config import MultiLevelConfig
from repro.core.vcycle import run_scratch, run_vcycle, saving_vs_baseline
from repro.data import MarkovLM, lm_batch


@pytest.fixture(scope="module")
def arena():
    cfg = tiny_dense(d_model=48, d_ff=96, vocab_size=128,
                     stages=tiny_dense().stages)
    tc = fast_tc(steps=60, batch_size=8, seq_len=24, log_every=2, peak_lr=3e-3)
    chain = MarkovLM(128)
    bf = lambda step: lm_batch(chain, 0, step, tc.batch_size, tc.seq_len)
    _, base = run_scratch(cfg, tc, bf, seed=0)
    return cfg, tc, bf, base


@pytest.mark.slow
def test_vcycle_saves_flops(arena):
    """The headline claim at proxy scale: the V-cycle reaches the baseline's
    final quality with fewer training FLOPs."""
    cfg, tc, bf, base = arena
    ml = MultiLevelConfig(n_levels=2, alpha=0.25, e_a_frac=0.05, e_small_frac=0.5)
    target = float(base.smoothed(5)[1][-1])
    out = run_vcycle(cfg, ml, tc, bf, seed=0, target_loss=target)
    s = saving_vs_baseline(base, out.history)
    assert np.isfinite(s["flops_saving"])
    assert s["flops_saving"] > 0.0, f"no saving: {s}"


@pytest.mark.slow
def test_alpha_one_locks_symmetric_neurons(arena):
    """The MECHANISM behind paper Table 5(C)/App. G: with alpha=1.0 (pure
    de-coalescing, no Interpolation) mirrored neuron pairs receive identical
    gradients forever, so the model trains with only half its effective
    width; alpha<1 breaks the tie immediately.

    (The end-to-end FLOPs-saving ordering of alpha=1.0 vs 0.25 is
    scale-dependent and does not reliably reproduce on a 48-dim/60-step
    proxy -- the capacity ceiling only binds for larger models; the
    quantitative ablation lives in benchmarks/table5.  The gradient-tie
    mechanism is exact at any scale and is what we pin here.)"""
    import jax.numpy as jnp

    from repro.core import operators as ops
    from repro.models.api import build_model, init_train_state, make_train_step

    cfg, tc, bf, base = arena
    cfg = cfg.replace(compute_dtype=jnp.float32, qk_norm=False, tie_embeddings=False)
    ml = MultiLevelConfig(n_levels=2)
    small_cfg = ops.coalesce_config(cfg, ml, width=True, depth=False)
    model, small = build_model(cfg), build_model(small_cfg)
    p_small = small.init(jax.random.PRNGKey(7))
    de = ops.make_decoalesce_fn(model.specs(), cfg, ml, width=True, depth=False)(p_small)

    def train_n(params, n=4):
        _, opt = init_train_state(model, tc, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(model, tc))
        for i in range(n):
            params, opt, _ = step(params, opt, bf(i))
        return params

    def pair_gap(params):
        w = np.asarray(params["stages"]["stage_0"]["b0"]["ffn"]["w_up"], np.float32)
        F = w.shape[-1]
        return float(np.abs(w[..., : F // 2] - w[..., F // 2:]).max())

    # alpha = 1.0: the de-coalesced model trains but mirrored pairs stay tied
    locked = train_n(de)
    assert pair_gap(locked) < 1e-5, "mirrored neurons must stay identical"
    # alpha = 0.25: interpolation with an independently-initialized large model
    p_large = model.init(jax.random.PRNGKey(8))
    mixed = ops.make_interpolate_fn(0.25)(p_large, de)
    broken = train_n(mixed)
    assert pair_gap(broken) > 1e-3, "interpolation must break the symmetry"


def test_serve_continuous_batching():
    from repro.launch.serve import Request, Server
    from repro.configs import get_config

    cfg = get_config("tinyllama-1.1b", smoke=True)
    srv = Server(cfg, batch=2, max_seq=48)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 100, size=5), max_new=4)
            for i in range(4)]
    done = srv.run(reqs)
    assert len(done) == 4
    assert all(len(r.out) == 4 for r in done)


@pytest.mark.slow
def test_train_launcher_resumes(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    root = os.path.join(os.path.dirname(__file__), "..")
    args = [sys.executable, "-m", "repro.launch.train", "--arch", "tinyllama-1.1b",
            "--smoke", "--steps", "8", "--batch", "2", "--seq", "16",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "4"]
    r1 = subprocess.run(args, capture_output=True, text=True, env=env, cwd=root, timeout=300)
    assert r1.returncode == 0, r1.stderr[-1500:]
    # second invocation resumes from the final checkpoint
    r2 = subprocess.run(args + ["--steps", "10"], capture_output=True, text=True,
                        env=env, cwd=root, timeout=300)
    assert r2.returncode == 0, r2.stderr[-1500:]
    assert "resumed from step" in r2.stdout


def test_watchdog_observes_slow_step():
    from repro.launch.train import Watchdog

    wd = Watchdog(factor=3.0)
    assert not any(wd.observe(0.01) for _ in range(10))
    assert wd.observe(0.1) is True  # 10x the median -> flagged
    assert wd.flagged == 1


def test_watchdog_median_excludes_current_sample():
    """Regression: the baseline median must be computed over PRIOR samples
    only.  With a bimodal window (25x10ms + 25x50ms, prior median 30ms) a
    100ms spike is > 3x the baseline -- but appending it first shifted the
    window median to 50ms, and the straggler masked itself."""
    from repro.launch.train import Watchdog

    wd = Watchdog(factor=3.0)
    for _ in range(25):
        wd.observe(0.01)
    for _ in range(25):
        wd.observe(0.05)
    assert wd.observe(0.1) is True


def test_vcycle_driver_heartbeats_every_step():
    """The module docstring promises the straggler watchdog on BOTH drivers;
    the V-cycle driver hangs it on the runner's per-step hook.  Every step is
    observed except each segment's first (its dt may carry the level's
    one-time jit compile, which is not a straggler signal)."""
    import repro.launch.train as T
    from repro.core.vcycle import segments

    seen = []
    orig = T.Watchdog.observe
    T.Watchdog.observe = lambda self, dt: (seen.append(dt), orig(self, dt))[1]
    try:
        cfg = tiny_dense(d_model=32, d_ff=64, vocab_size=128)
        tc = fast_tc(steps=6, log_every=10)
        ml = MultiLevelConfig(n_levels=2)
        T.train_vcycle_ckpt(cfg, ml, tc, ckpt=None, ckpt_every=0, verbose=False)
    finally:
        T.Watchdog.observe = orig
    plan = segments(cfg, ml, tc)
    assert len(seen) == sum(p.steps for p in plan) - len(plan)


def test_train_plain_heartbeats_every_step(monkeypatch):
    """Regression: with log_every > 1 the watchdog used to see only every
    log_every-th step, hiding most stragglers."""
    import repro.launch.train as T

    seen = []
    orig = T.Watchdog.observe

    def spying(self, dt):
        seen.append(dt)
        return orig(self, dt)

    monkeypatch.setattr(T.Watchdog, "observe", spying)
    cfg = tiny_dense(d_model=32, d_ff=64, vocab_size=128)
    tc = fast_tc(steps=5, log_every=10)
    T.train_plain(cfg, tc, ckpt=None, ckpt_every=0, verbose=False)
    assert len(seen) == 5


@pytest.mark.slow
def test_vcycle_launcher_sigterm_checkpoints(tmp_path):
    """Preemption awareness: SIGTERM must trigger ONE final blocking
    checkpoint and a clean exit 0, even though the --ckpt-every cadence
    (1000) would never fire; the restart resumes from that save."""
    env = dict(os.environ, PYTHONPATH="src")
    root = os.path.join(os.path.dirname(__file__), "..")
    args = [sys.executable, "-m", "repro.launch.train", "--arch", "tinyllama-1.1b",
            "--smoke", "--vcycle", "--levels", "2", "--steps", "40",
            "--batch", "2", "--seq", "16",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "1000"]
    log = os.path.join(str(tmp_path), "run.log")
    with open(log, "w") as lf:
        p = subprocess.Popen(args, env=env, cwd=root, stdout=lf,
                             stderr=subprocess.STDOUT)
        deadline = time.time() + 240
        stepping = False
        while time.time() < deadline and p.poll() is None and not stepping:
            with open(log) as f:
                stepping = "coalescing" in f.read()  # past the first segment
            time.sleep(0.05)
        assert stepping, "run never reached the first transition"
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=240) == 0, "SIGTERM exit was not clean"
    out = open(log).read()
    assert "[preempt] SIGTERM: blocking V-cycle checkpoint" in out, out[-1500:]
    manifest = os.path.join(str(tmp_path), "manifest.json")
    assert os.path.exists(manifest), "preemption save never published"
    r = subprocess.run(args, capture_output=True, text=True, env=env, cwd=root,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "resumed at phase=" in r.stdout, r.stdout[-1500:]


def _load_final_params(ckpt_dir: str):
    import json

    from repro.checkpoint.manager import _read_leaves

    m = json.load(open(os.path.join(ckpt_dir, "manifest.json")))
    assert m["meta"].get("phase") == "done", m["meta"]
    # layout-agnostic: v3 manifests resolve through the object pool, v2 dirs
    # through whole-leaf files
    return _read_leaves(os.path.join(ckpt_dir, m["dir"], "params"))


@pytest.mark.slow
def test_vcycle_launcher_mesh_kill_resume_cross_mesh(tmp_path):
    """The acceptance drill: a --mesh 1x2 V-cycle run SIGKILLed
    mid-upward-sweep resumes under --mesh 2x1 and reproduces the
    uninterrupted run's final params (the launcher forces CPU host devices
    itself, so no XLA_FLAGS in the parent)."""
    import json

    env = dict(os.environ, PYTHONPATH="src")
    root = os.path.join(os.path.dirname(__file__), "..")
    common = [sys.executable, "-m", "repro.launch.train", "--arch",
              "tinyllama-1.1b", "--smoke", "--vcycle", "--levels", "2",
              "--steps", "20", "--batch", "4", "--seq", "16", "--f32",
              "--ckpt-every", "2"]
    ref_dir, ck_dir = str(tmp_path / "ref"), str(tmp_path / "ck")

    r = subprocess.run(common + ["--mesh", "1x2", "--ckpt-dir", ref_dir],
                       capture_output=True, text=True, env=env, cwd=root,
                       timeout=480)
    assert r.returncode == 0, r.stderr[-1500:]

    p = subprocess.Popen(common + ["--mesh", "1x2", "--ckpt-dir", ck_dir],
                         env=env, cwd=root, stdout=subprocess.DEVNULL,
                         stderr=subprocess.DEVNULL)
    manifest = os.path.join(ck_dir, "manifest.json")
    deadline = time.time() + 240
    phase = None
    try:
        while time.time() < deadline and p.poll() is None and phase != "up":
            try:
                phase = json.load(open(manifest))["meta"].get("phase")
            except (OSError, ValueError):
                pass
            time.sleep(0.02)
        assert phase == "up", f"never saw an upward-sweep checkpoint ({phase})"
    finally:
        if p.poll() is None:
            os.kill(p.pid, signal.SIGKILL)
        p.wait(timeout=60)

    r2 = subprocess.run(common + ["--mesh", "2x1", "--ckpt-dir", ck_dir],
                        capture_output=True, text=True, env=env, cwd=root,
                        timeout=480)
    assert r2.returncode == 0, r2.stderr[-1500:]
    assert "resumed at phase=up" in r2.stdout, r2.stdout[-1500:]

    ref, got = _load_final_params(ref_dir), _load_final_params(ck_dir)
    assert ref.keys() == got.keys()
    for k in ref:
        np.testing.assert_allclose(got[k].astype(np.float64),
                                   ref[k].astype(np.float64), atol=1e-3,
                                   err_msg=k)


@pytest.mark.slow
def test_vcycle_launcher_sigkill_resume(tmp_path):
    """The real CLI path: start a V-cycle run, SIGKILL it once the first
    checkpoint lands, restart with identical args and require the
    (phase, level, step) resume line."""
    env = dict(os.environ, PYTHONPATH="src")
    root = os.path.join(os.path.dirname(__file__), "..")
    args = [sys.executable, "-m", "repro.launch.train", "--arch", "tinyllama-1.1b",
            "--smoke", "--vcycle", "--levels", "2", "--steps", "40",
            "--batch", "2", "--seq", "16",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "3"]
    p = subprocess.Popen(args, env=env, cwd=root, stdout=subprocess.DEVNULL,
                         stderr=subprocess.DEVNULL)
    manifest = os.path.join(str(tmp_path), "manifest.json")
    deadline = time.time() + 240
    try:
        while (time.time() < deadline and p.poll() is None
               and not os.path.exists(manifest)):
            time.sleep(0.05)
        assert os.path.exists(manifest), "no checkpoint before timeout/exit"
    finally:
        if p.poll() is None:
            os.kill(p.pid, signal.SIGKILL)
        p.wait(timeout=60)
    r = subprocess.run(args, capture_output=True, text=True, env=env, cwd=root,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "resumed at phase=" in r.stdout, r.stdout[-1500:]


@pytest.mark.slow
def test_serve_soak_live_trainer_reloads(tmp_path):
    """The train->serve soak drill: a REAL ``python -m repro.launch.train
    --vcycle`` run publishes a checkpoint every 2 global steps while an
    in-process paged server with an attached ManifestWatcher serves
    continuous traffic from the same directory.  The server must swap
    multiple published steps in publish order, skip any coalesced
    mid-V-cycle publishes it examines, drop zero requests (every request
    completes its full token budget), and land reloads by digest diff
    (``last_gather_stats`` shows pruned transfers)."""
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config
    from repro.launch.serve import ManifestWatcher, Request, make_server

    ckpt = str(tmp_path / "ckpt")
    env = dict(os.environ, PYTHONPATH="src")
    root = os.path.join(os.path.dirname(__file__), "..")
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "tinyllama-1.1b", "--smoke", "--vcycle", "--levels", "2",
            "--steps", "24", "--batch", "2", "--seq", "16",
            "--ckpt-dir", ckpt, "--ckpt-every", "2"]

    cfg = get_config("tinyllama-1.1b", smoke=True)
    srv = make_server(cfg, engine="paged", batch=3, max_seq=48, page_size=8)
    watcher = ManifestWatcher(CheckpointManager(ckpt), like=srv.params)
    srv.attach_watcher(watcher)

    rng = np.random.default_rng(0)
    rid = 0

    def wave():
        nonlocal rid
        reqs = [Request(rid=rid + i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=int(rng.integers(4, 12))),
                        max_new=4) for i in range(3)]
        rid += 3
        srv.run(reqs)

    log = str(tmp_path / "train.log")
    with open(log, "w") as lf:
        trainer = subprocess.Popen(args, env=env, cwd=root, stdout=lf,
                                   stderr=subprocess.STDOUT)
        try:
            deadline = time.time() + 600
            while trainer.poll() is None and time.time() < deadline:
                wave()  # continuous traffic while the trainer publishes
        finally:
            if trainer.poll() is None:
                trainer.kill()
        assert trainer.wait(timeout=60) == 0, open(log).read()[-1500:]
    wave()  # one more wave to land the trainer's terminal save

    # zero dropped requests: everything admitted, everything completed full
    assert srv.rejected == []
    assert len(srv.done) == rid
    assert all(len(r.out) == 4 for r in srv.done)

    # the server really followed the trainer: >= 2 live swaps, publish order
    assert srv.reloads == len(watcher.steps_seen), \
        (srv.reloads, watcher.steps_seen)
    assert len(watcher.steps_seen) >= 2, watcher.steps_seen
    assert watcher.steps_seen == sorted(set(watcher.steps_seen)), \
        "manifest steps landed out of order"
    # skipped (coalesced-shape) steps never served, never landed
    assert not set(watcher.steps_skipped) & set(watcher.steps_seen)
    # digest-diff transfers: the gathers were pruned to the needed digests
    assert any(r["gather_skipped"] > 0 for r in watcher.reload_history), \
        watcher.reload_history
    assert watcher.poll_errors == 0 or watcher.steps_seen, \
        "poll errors without a single landed step"


@pytest.mark.parametrize("lone", [False, True], ids=["no_tpu", "lone_script"])
def test_chip_smoke_refuses_to_run_off_chip(tmp_path, lone):
    """chip_smoke.py has no CPU fallback: without a TPU (or copied out of
    the checkout, with no package beside it) it exits non-zero and prints
    no result line."""
    root = os.path.join(os.path.dirname(__file__), "..")
    script = os.path.join(root, "chip_smoke.py")
    if lone:
        script = str(tmp_path / "chip_smoke.py")
        with open(os.path.join(root, "chip_smoke.py")) as src, \
                open(script, "w") as dst:
            dst.write(src.read())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(script),
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert ("no TPU found" in out.stderr) != lone
