#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; run on the chip,
never by the benchmark's own runs.

    python3 benchmarks/chip/calibrate.py --workload bert-large.vcycle \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --controls 3 --out readings.jsonl

For every seed, the program's first steps at both levels against the
reference's (``run: program``).  On the first ``--controls`` seeds also the
train-step control (the reference in fp8, ``control_fp8_step``), the
coalescing control (pair sums in bfloat16, ``control_bf16_coalesce``) and
the planted fault "half of the batch left out" (``fault_half_batch``).  Each
line holds the numbers of ``compare.py`` and the raw per-leaf norms they are
worked out from, so that a number can be changed without a new run.  One
JSON object per line on standard output (and appended to ``--out``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import harness  # noqa: E402


def emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def raw(r):
    return {k: r[k] for k in ("l0", "l1")}


def train(args, seeds):
    import jax.numpy as jnp

    kind = harness.load_module("kinds", "train.py")
    compare = harness.load_module("compare.py")
    for j, seed in enumerate(seeds):
        ctx = harness.make_ctx(args.workload, seed, 0.0, False, time.perf_counter())
        t = time.perf_counter()
        st, prog = kind.program_side(ctx)
        del st
        t_prog = time.perf_counter() - t
        refr = kind.reference_side(seed, ctx.config, ctx.workload)
        t_ref = time.perf_counter() - t - t_prog
        emit(args.out, {"seed": seed, "run": "program", "numbers": dict(kind.numbers(prog, refr)),
                        "raw": raw(prog), "reference": raw(refr),
                        "program_s": t_prog, "reference_s": t_ref})
        if j >= args.controls:
            continue
        ctrl = kind.reference_side(seed, ctx.config, ctx.workload, mode="fp8")
        emit(args.out, {"seed": seed, "run": "control_fp8_step",
                        "numbers": dict(kind.numbers(ctrl, refr)), "raw": raw(ctrl)})
        coal = kind.reference_coalesced(seed, ctx.config, jnp.bfloat16)
        emit(args.out, {"seed": seed, "run": "control_bf16_coalesce",
                        "numbers": {"coalesce_gap": compare.coalesce_gap(coal, refr["coalesced"])}})
        half = kind.reference_side(seed, ctx.config, ctx.workload, half=True)
        emit(args.out, {"seed": seed, "run": "fault_half_batch",
                        "numbers": dict(kind.numbers(half, refr)), "raw": raw(half)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    harness.enable_compile_cache()
    try:
        harness.device_info(1)
    except harness.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 1
    train(args, [int(s) for s in args.seeds.split(",") if s])
    return 0


if __name__ == "__main__":
    sys.exit(main())
