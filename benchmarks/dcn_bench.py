"""DCN gradient-reduction benchmark: dense vs hierarchical int8+EF all-reduce,
as an APPEND-ONLY perf trajectory (``benchmarks/results/BENCH_dcn.json``).

A single process forces 2 host devices and runs the full V-cycle twice over a
("pod", "data", "model") = (2, 1, 1) mesh -- the pod axis standing in for the
DCN (between-pods) dimension where bandwidth dominates:

  * ``dense``   -- the explicit shard_map reduction, f32 pmean over pod+data.
  * ``int8_ef`` -- hierarchical reduction: the DCN hop carries the packed
                   int8 error-feedback payload (``ef_int8_psum``).

Each invocation appends one trajectory point recording:

  * **bytes-on-wire per step over the DCN axis**, analytic, per V-cycle level
    (the gradient tree is level-shaped, so the coalesced levels ship fewer
    bytes twice over): f32 elements vs int8 elements + one f32 scale per
    leaf.  The schedule-weighted overall ratio is the headline number --
    dtype-exact arithmetic, so it is hardware-independent.
  * **the trace probe**: how many compiled steps actually contain
    ``ef_int8_psum`` (acceptance is "asserted via call probe, not config").
  * **loss-trajectory deviation** between the two runs: int8+EF must track
    dense within quantization noise or the compression is eating signal.

``--check-regression`` gates the invariants (exit 1 on violation): probe > 0,
overall wire ratio >= --min-ratio (default 3x), max loss deviation <=
--loss-tol.  All three are hardware-independent, so a laptop, CI runner and
TPU host share one trajectory file.

Smoke scale by default: runs on CPU in about a minute (the CI ``dcn-drill``
job runs exactly this).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List

BENCH_PATH = os.path.join(os.path.dirname(__file__), "results", "BENCH_dcn.json")


def _load_trajectory() -> List[Dict]:
    if not os.path.exists(BENCH_PATH):
        return []
    with open(BENCH_PATH) as f:
        return json.load(f)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12,
                    help="top-level V-cycle step budget (smoke scale)")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="kept for CLI symmetry with the other benches")
    ap.add_argument("--min-ratio", type=float, default=3.0,
                    help="required DCN bytes-on-wire reduction (dense/int8)")
    ap.add_argument("--loss-tol", type=float, default=5e-2,
                    help="max allowed |dense - int8_ef| loss deviation")
    ap.add_argument("--check-regression", action="store_true",
                    help="fail (exit 1) when the probe never fires, the wire "
                         "ratio is < --min-ratio, or the int8_ef loss "
                         "trajectory drifts > --loss-tol from dense")
    args = ap.parse_args()

    # 2 host devices BEFORE the backend initializes: the pod axis needs rank 2
    from repro.launch.mesh import ensure_host_devices, make_mesh

    ensure_host_devices(2)

    import jax
    import numpy as np

    from benchmarks.common import batch_fn_for
    from repro.config import (BlockSpec, ModelConfig, MultiLevelConfig,
                              TrainConfig, uniform_stages)
    from repro.core.vcycle import VCycleRunner
    from repro.distributed.compression import (dense_wire_bytes,
                                               ef_psum_calls,
                                               int8_wire_bytes,
                                               reset_ef_psum_probe)

    baseline = _load_trajectory()  # read BEFORE appending

    import jax.numpy as jnp

    cfg = ModelConfig(name="dcn-bench", family="dense", d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=64, vocab_size=128,
                      stages=uniform_stages(3, BlockSpec("attn", "dense")),
                      qk_norm=True, remat="none", attn_impl="plain",
                      compute_dtype=jnp.float32)
    tc = TrainConfig(steps=args.steps, warmup_steps=1, peak_lr=3e-4,
                     batch_size=4, seq_len=16, log_every=2)
    ml = MultiLevelConfig(n_levels=2, alpha=0.25, e_a_frac=0.25,
                          e_small_frac=0.5)
    mesh = make_mesh((2, 1, 1), ("pod", "data", "model"))
    bf = batch_fn_for(cfg, tc)

    runs: Dict[str, Dict] = {}
    outs = {}
    for mode in ("dense", "int8_ef"):
        reset_ef_psum_probe()
        runner = VCycleRunner(
            cfg, ml, dataclasses.replace(tc, grad_compression=mode),
            bf, seed=0, mesh=mesh)
        t0 = time.time()
        outs[mode] = runner.run()
        runs[mode] = {"seconds": time.time() - t0,
                      "final_loss": float(outs[mode].history.loss[-1]),
                      "probe_traced_steps": ef_psum_calls()}
        print(f"[dcn_bench] {mode}: {runs[mode]['seconds']:.1f}s "
              f"final_loss={runs[mode]['final_loss']:.4f} "
              f"probe={runs[mode]['probe_traced_steps']}", flush=True)

    probe = runs["int8_ef"]["probe_traced_steps"]
    max_dev = float(np.max(np.abs(
        np.asarray(outs["dense"].history.loss)
        - np.asarray(outs["int8_ef"].history.loss))))

    # analytic DCN bytes-on-wire per step, per level (grad tree == param tree)
    plan = runner.plan
    levels: Dict[int, Dict] = {}
    for level in sorted({p.level for p in plan}):
        shapes = jax.eval_shape(runner.models[level].init, jax.random.PRNGKey(0))
        d, c = dense_wire_bytes(shapes), int8_wire_bytes(shapes)
        levels[level] = {"dense_bytes_per_step": int(d),
                         "int8_bytes_per_step": int(c),
                         "ratio": d / c}
    total_d = sum(p.steps * levels[p.level]["dense_bytes_per_step"] for p in plan)
    total_c = sum(p.steps * levels[p.level]["int8_bytes_per_step"] for p in plan)
    overall = total_d / total_c
    per_level = ", ".join(f"l{k}={v['ratio']:.2f}x"
                          for k, v in sorted(levels.items()))
    print(f"[dcn_bench] wire ratio overall={overall:.2f}x ({per_level}) "
          f"max_loss_dev={max_dev:.4f}", flush=True)

    entry = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": jax.default_backend(),
        "mesh": list(mesh.devices.shape),
        "steps": args.steps,
        "runs": runs,
        "max_loss_dev": max_dev,
        "wire": {"levels": {str(k): v for k, v in levels.items()},
                 "schedule_dense_bytes": int(total_d),
                 "schedule_int8_bytes": int(total_c),
                 "overall_ratio": overall},
    }
    os.makedirs(os.path.dirname(BENCH_PATH), exist_ok=True)
    with open(BENCH_PATH, "w") as f:
        json.dump(baseline + [entry], f, indent=1, default=float)
    print(f"[dcn_bench] appended trajectory point #{len(baseline) + 1} "
          f"-> {BENCH_PATH}", flush=True)

    if args.check_regression:
        failures = []
        if probe <= 0:
            failures.append("ef_int8_psum never traced into a compiled step")
        if runs["dense"]["probe_traced_steps"] != 0:
            failures.append("dense run touched the compressed path")
        if overall < args.min_ratio:
            failures.append(f"wire ratio {overall:.2f} < {args.min_ratio}")
        if max_dev > args.loss_tol:
            failures.append(f"loss deviation {max_dev:.4f} > {args.loss_tol}")
        if failures:
            for msg in failures:
                print(f"[dcn_bench] REGRESSION: {msg}", flush=True)
            return 1
        print("[dcn_bench] regression gate passed", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
