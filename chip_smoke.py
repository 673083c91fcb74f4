#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: the five Pallas kernels, V-cycle
training and paged serving, at GPT-Base width, in one process.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the mesh paths on four chips

One chip runs three phases:

1. kernels -- each registry op through ``dispatch`` with ``backend="pallas"``
   at GPT-Base shapes, checked to lower to a Mosaic kernel
   (``tpu_custom_call``) and compared with its ``xla`` backend;
2. training -- ``launch.train.train_vcycle_ckpt`` (what ``launch/train.py``
   runs for ``--vcycle``) on ``gpt-base`` with a 2-level V-cycle: level-0
   steps, coalescing, level-1 steps, de-coalescing with interpolation, level-0
   steps again, every loss finite, and the final checkpoint restored
   bit-identically;
3. serving -- ``make_server(engine="paged")`` with the trained weights
   (``set_params``) answers 8 requests of 16-512 prompt tokens, each with
   exactly ``max_new`` tokens that agree with a full forward pass; the
   16-token prompts' cold prefill runs ``attention_tile``.

``--chips 4`` runs only what exists across chips: the phase-2 V-cycle on a
(2, 2) ("data", "model") mesh against the same steps unsharded, and paged
decode on a 1x4 mesh against the unsharded engine.

Weights come from ``--seed``, data from ``MarkovLM``; nothing is downloaded.
Times and memory are printed for information only; they are not metrics.
The last line of standard output is one JSON object, printed only when
every phase passed; without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at.  The defaults are GPT-Base's published widths
    (configs/paper_models.py) and the paper's training shape."""

    arch: str = "gpt-base"
    batch: int = 8
    seq_len: int = 1024
    steps: int = 46          # E_a = round(0.033 * 46) = 2 steps per level-0 init
    serve_batch: int = 4
    max_seq: int = 1024
    max_new: int = 32
    prompt_lens: tuple = (16, 512, 100, 300, 16, 512, 100, 300)


class Clock:
    """Wall time of a phase and the compile time inside it (JAX's own
    trace/lower/compile events)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.compile_s += duration

    def phase(self, name: str):
        return _Phase(self, name)


class _Phase:
    def __init__(self, clock: Clock, name: str):
        self.clock, self.name = clock, name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.compile_s
        log(f"phase {self.name}: start")
        return self

    def __exit__(self, exc_type, exc, tb):
        import jax

        wall = time.perf_counter() - self.t0
        comp = self.clock.compile_s - self.c0
        stats = jax.devices()[0].memory_stats() or {}
        log(f"phase {self.name}: {'ok' if exc is None else 'FAILED'}; "
            f"wall {wall:.1f} s, of which compile {comp:.1f} s; "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")
        return False


# ---------------------------------------------------------------------------
# phase 1: kernels


def _max_err(got, want) -> tuple:
    import jax
    import numpy as np

    g = [np.asarray(x, np.float32) for x in jax.tree.leaves(got)]
    w = [np.asarray(x, np.float32) for x in jax.tree.leaves(want)]
    err = max(float(np.max(np.abs(a - b))) for a, b in zip(g, w))
    scale = max(float(np.max(np.abs(b))) for b in w)
    return err, scale


def kernel_phase(cfg, sz: Sizes, seed: int) -> None:
    """Each registry op: pallas (must hold a Mosaic kernel) vs its xla oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import dispatch

    E, H, D, F, L = (cfg.d_model, cfg.n_heads, cfg.resolved_head_dim, cfg.d_ff,
                     cfg.n_layers)
    V = cfg.padded_vocab
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dt):
        return jax.random.normal(next(ks), shape, dt)

    page = 16
    M = sz.max_seq // page
    N = sz.serve_batch * M + 1
    rng = np.random.default_rng(seed)
    tables = jnp.asarray((rng.permutation(N - 1) + 1)[:sz.serve_batch * M]
                         .reshape(sz.serve_batch, M), jnp.int32)
    lengths = jnp.asarray(np.linspace(1, sz.max_seq, sz.serve_batch).astype(int),
                          jnp.int32)
    qkv = [normal((sz.batch, H, sz.seq_len, D), jnp.bfloat16) for _ in range(3)]
    ct = normal((sz.batch, H, sz.seq_len, D), jnp.bfloat16)
    # attention_tile (inputs of their own key): a 16-token cold prefill, and
    # short training rows
    tk = iter(jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), 1), 7))
    rows = lambda b, s: jax.random.normal(next(tk), (b, s, H * D), jnp.bfloat16)
    prompt = min(sz.prompt_lens)
    tile_fwd = [rows(1, prompt) for _ in range(3)]
    tile_fwd.append(jnp.arange(prompt, dtype=jnp.int32)[None])
    tile_rows = [rows(sz.batch, 128) for _ in range(3)]
    tile_rows.append(jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32),
                                      (sz.batch, 128)))
    tile_ct = rows(sz.batch, 128)
    tile_kw = dict(head_dim=D, causal=True, scale=D ** -0.5, theta=cfg.rope_theta)

    def flash_grads(impl):
        def loss(q, k, v):
            o = impl(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) * ct.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))

    def tile_grads(impl):
        def loss(q, k, v, positions):
            o = impl(q, k, v, positions, **tile_kw)
            return jnp.sum(o.astype(jnp.float32) * tile_ct.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))

    # (op, label, build(impl) -> fn, args, relative tolerance, kernels >= n)
    cases = [
        ("interp_axpy", "interp_axpy [L,E,F] f32",
         lambda impl: functools.partial(impl, alpha=0.25),
         (normal((L, E, F), jnp.float32), normal((L, E, F), jnp.float32)), 1e-6, 1),
        ("coalesce_pair", "coalesce_pair [E,V] f32 axis 0",
         lambda impl: functools.partial(impl, axis=0, w0=0.5),
         (normal((E, V), jnp.float32),), 1e-6, 1),
        ("flash_attention", "flash_attention fwd [B,H,S,D] bf16 causal",
         lambda impl: functools.partial(impl, causal=True), qkv, 2e-2, 1),
        ("flash_attention", "flash_attention vjp dq,dk,dv bf16 causal",
         flash_grads, qkv, 2e-2, 3),
        ("attention_tile", f"attention_tile fwd [1,{prompt},H*D] bf16 causal",
         lambda impl: functools.partial(impl, **tile_kw), tile_fwd, 2e-2, 1),
        ("attention_tile", "attention_tile vjp dq,dk,dv [B,128,H*D] bf16 causal",
         tile_grads, tile_rows, 2e-2, 2),
        ("paged_attention_decode", "paged_attention_decode [B,KH,P,D] bf16",
         lambda impl: impl,
         (normal((sz.serve_batch, H, 1, D), jnp.bfloat16),
          normal((N, H, page, D), jnp.bfloat16),
          normal((N, H, page, D), jnp.bfloat16), tables, lengths), 2e-2, 1),
    ]
    for op, label, build, args, rtol, n_kernels in cases:
        fn = jax.jit(build(dispatch.get_impl(op, "pallas")))
        text = fn.lower(*args).compile().as_text()
        n = text.count("tpu_custom_call")
        check(n >= n_kernels, f"{label}: {n} Mosaic kernels in the compiled "
              f"program, expected >= {n_kernels}")
        got = fn(*args)
        want = jax.jit(build(dispatch.get_impl(op, "xla")))(*args)
        err, scale = _max_err(got, want)
        tol = rtol * max(scale, 1.0)
        log(f"kernel {label}: pallas vs xla max_err {err:.3e} "
            f"(tol {tol:.3e} = {rtol:g} x max(1, max|ref| {scale:.3e})); "
            f"{n} tpu_custom_call")
        check(err <= tol, f"{label}: max_err {err} > tol {tol}")


# ---------------------------------------------------------------------------
# phase 2: V-cycle training


def train_phase(cfg, sz: Sizes, seed: int, mesh=None, ckpt_dir=None):
    """``train_vcycle_ckpt`` over a full 2-level V-cycle; returns its output.

    With ``ckpt_dir`` the final checkpoint is restored and must equal the
    trained params bit for bit."""
    import jax
    import numpy as np

    from repro.checkpoint import CheckpointManager
    from repro.config import MultiLevelConfig, TrainConfig
    from repro.core.vcycle import segments
    from repro.launch.train import train_vcycle_ckpt
    from repro.models.api import build_model, zero_train_state

    ml = MultiLevelConfig(n_levels=2, alpha=0.25)
    tc = TrainConfig(steps=sz.steps, warmup_steps=2, batch_size=sz.batch,
                     seq_len=sz.seq_len, seed=seed, log_every=1)
    plan = segments(cfg, ml, tc)
    log("schedule: " + ", ".join(f"{p.phase}@level{p.level}x{p.steps}"
                                 for p in plan))
    check(all(p.steps >= 2 for p in plan), f"a segment has < 2 steps: {plan}")
    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    out = train_vcycle_ckpt(cfg, ml, tc, ckpt=ckpt, ckpt_every=0, mesh=mesh,
                            verbose=True)
    h = out.history
    want_levels = [p.level for p in plan for _ in range(p.steps)]
    check(h.level == want_levels, f"levels trained {h.level} != schedule")
    check(all(np.isfinite(h.loss)), f"non-finite loss: {h.loss}")
    log(f"losses: first {h.loss[0]:.4f}, level-1 first "
        f"{h.loss[plan[0].steps]:.4f}, after interpolation "
        f"{h.loss[plan[0].steps + plan[1].steps]:.4f}, last {h.loss[-1]:.4f} "
        f"({len(h.loss)} steps, all finite)")
    if ckpt is not None:
        like, _ = zero_train_state(build_model(cfg), tc)
        restored, meta = ckpt.restore({"params": like})
        check(meta.get("phase") == "done", f"final checkpoint meta {meta}")
        a = jax.tree.leaves(jax.device_get(out.params))
        b = jax.tree.leaves(jax.device_get(restored["params"]))
        same = all(x.dtype == y.dtype and np.array_equal(x, y)
                   for x, y in zip(a, b))
        check(same and len(a) == len(b), "restored params differ from trained")
        log(f"final checkpoint (step {meta['step']}) restored bit-identically "
            f"({len(a)} leaves)")
    return out


# ---------------------------------------------------------------------------
# phase 3: paged serving


def _requests(cfg, sz: Sizes, seed: int):
    import numpy as np

    from repro.launch.serve import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n,
                                               dtype=np.int32),
                    max_new=sz.max_new)
            for i, n in enumerate(sz.prompt_lens)]


def serve(cfg, sz: Sizes, params, seed: int, mesh=None):
    """The paged engine with ``params`` swapped in; returns (server, done)."""
    from repro.launch.serve import make_server

    srv = make_server(cfg, engine="paged", batch=sz.serve_batch,
                      max_seq=sz.max_seq, mesh=mesh)
    srv.set_params(params)
    done = srv.run(_requests(cfg, sz, seed))
    check(len(done) == len(sz.prompt_lens) and not srv.rejected,
          f"{len(done)} of {len(sz.prompt_lens)} requests answered, "
          f"{len(srv.rejected)} rejected")
    for r in done:
        check(len(r.out) == r.max_new, f"request {r.rid}: {len(r.out)} tokens, "
              f"expected {r.max_new}")
        check(all(0 <= t < cfg.padded_vocab for t in r.out),
              f"request {r.rid}: token out of range")
    return srv, done


def serve_phase(cfg, sz: Sizes, params, seed: int, min_agree: float = 0.8):
    """Serve, then teacher-force every answer through one full forward pass:
    the greedy tokens must mostly be its argmaxes (bf16 near-ties may flip a
    few)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.api import build_model

    _, done = serve(cfg, sz, params, seed)
    width = max(len(r.prompt) + r.max_new - 1 for r in done)
    toks = np.zeros((len(done), width), np.int32)
    for i, r in enumerate(done):
        seq = list(r.prompt) + r.out[:-1]
        toks[i, :len(seq)] = seq
    logits = jax.jit(build_model(cfg).forward_logits)(
        params, {"tokens": jnp.asarray(toks)})
    logits = np.asarray(logits, np.float32)
    agree = total = 0
    gaps, spread = [], []
    for i, r in enumerate(done):
        L = len(r.prompt)
        lg = logits[i, L - 1:L - 1 + r.max_new]  # [max_new, V]
        served = lg[np.arange(r.max_new), np.asarray(r.out)]
        agree += int(np.sum(served == lg.max(-1)))
        total += r.max_new
        gaps.append(lg.max(-1) - served)  # 0 where the argmax was served
        spread.append(lg.max(-1) - lg.mean(-1))
    rate = agree / total
    lens = sorted({len(r.prompt) for r in done})
    log(f"serving: {len(done)} requests, prompts {lens}, "
        f"{total} tokens; {agree}/{total} = {rate:.3f} agree with the "
        f"teacher-forced forward argmax (min {min_agree}); served token's "
        f"logit below the max by at most {np.max(gaps):.3e} (max - mean "
        f"logit {np.mean(spread):.3e} on average)")
    check(rate >= min_agree, f"agreement {rate:.3f} < {min_agree}")


# ---------------------------------------------------------------------------
# four chips


def four_chip_phase(cfg, sz: Sizes, seed: int, loss_tol: float = 0.05,
                    min_agree: float = 0.95) -> None:
    """The V-cycle on a (2, 2) mesh vs unsharded, and paged decode on a 1x4
    mesh vs the unsharded engine, in this one process."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed import mesh_ctx
    from repro.launch.mesh import make_cli_mesh

    ref = train_phase(cfg, sz, seed)
    mesh = make_cli_mesh("2x2")
    with mesh_ctx(mesh):
        out = train_phase(cfg, sz, seed, mesh=mesh)
    leaves = jax.tree.leaves(out.params)
    spans = [len(x.sharding.device_set) for x in leaves]
    split = sum(not x.sharding.is_fully_replicated for x in leaves)
    log(f"2x2 V-cycle: {len(leaves)} param leaves, every one on "
        f"{min(spans)}..{max(spans)} devices, {split} partitioned")
    check(min(spans) == 4 and split > 0, "mesh params are not spread over 4 chips")
    d = np.abs(np.asarray(out.history.loss) - np.asarray(ref.history.loss))
    log("per-step loss, 2x2 mesh vs one chip: " + " ".join(
        f"{s}:{a:.4f}/{b:.4f}" for s, a, b in zip(
            ref.history.step, out.history.loss, ref.history.loss)))
    log(f"max |loss(2x2) - loss(1 chip)| {d.max():.3e} over {len(d)} steps "
        f"(tol {loss_tol}, bf16 activations)")
    check(d.max() <= loss_tol, f"2x2 loss deviates by {d.max()} > {loss_tol}")

    # decode in f32 at full matmul precision, so sharded and unsharded logits
    # differ only in summation order: the default one-pass bf16 products flip
    # the near-tied argmaxes of this barely trained model
    scfg = cfg.replace(compute_dtype=jnp.float32)
    pmesh = make_cli_mesh("1x4")
    with jax.default_matmul_precision("highest"):
        _, base = serve(scfg, sz, ref.params, seed)
        srv, sharded = serve(scfg, sz, ref.params, seed, mesh=pmesh)
    pool = jax.tree.leaves(srv.pages)
    check(all(len(x.sharding.device_set) == 4 for x in pool)
          and all(not x.sharding.is_fully_replicated for x in pool),
          "page pool is not sharded over 4 chips")
    same = sum(int(a == b) for r, s in zip(base, sharded)
               for a, b in zip(r.out, s.out))
    total = sum(r.max_new for r in base)
    log(f"1x4 paged decode: {len(pool)} pool leaves each split over 4 chips; "
        f"{same}/{total} = {same / total:.3f} tokens equal to the unsharded "
        f"engine (min {min_agree})")
    check(same / total >= min_agree, f"sharded decode agreement {same / total}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh phases, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: the repro package is not at {src}; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is {backend!r}); this "
              f"smoke has no CPU fallback", file=sys.stderr)
        return 1
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: {dev}; jax {jax.__version__}; compile cache {cache}")
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.kernels import dispatch

    sz = Sizes()
    # at 1024 tokens the shapes put the flash kernels in the training step
    cfg = get_config(sz.arch)
    log(f"config {cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads, "
        f"{cfg.n_layers} layers, vocab {cfg.vocab_size} (padded "
        f"{cfg.padded_vocab}), attn_impl {cfg.attn_impl}; batch {sz.batch} x "
        f"{sz.seq_len}")
    clock = Clock()
    try:
        if args.chips == 4:
            with clock.phase("four-chip"):
                four_chip_phase(cfg, sz, args.seed)
        else:
            with clock.phase("kernels"):
                kernel_phase(cfg, sz, args.seed)
            dispatch.reset_traced()
            with clock.phase("training"), tempfile.TemporaryDirectory() as d:
                out = train_phase(cfg, sz, args.seed, ckpt_dir=d)
            with clock.phase("serving"):
                serve_phase(cfg, sz, out.params, args.seed)
        ran = dispatch.traced()
        log("implementations traced: " + ", ".join(
            f"{op}={b} x{n}" for (op, b), n in sorted(ran.items())))
        off = sorted({f"{op}={b}" for (op, b) in ran if b != "pallas"})
        check(not off, f"ops left the Pallas kernels: {off}")
        want = {"coalesce_pair", "interp_axpy", "flash_attention",
                "paged_attention_decode", "attention_tile"}
        check(want <= {op for op, _ in ran},
              f"ops never traced: {sorted(want - {op for op, _ in ran})}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
