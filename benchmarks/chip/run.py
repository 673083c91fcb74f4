#!/usr/bin/env python3
"""Runs one cell of the chip benchmark once and prints its result.

    python3 benchmarks/chip/run.py --workload bert-large.vcycle --seed 7 \\
        --seconds 10 --trace 0

The cell is ``workloads/<name>.json``; ``BENCHMARK.json`` at the root of the
checkout says which metrics it reports (end-to-end ones with ``--trace 0``,
per-layer ones with ``--trace 1``).  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` when traced), then ``compared``, each number checked for
``correct`` beside its limit.  Without a TPU, or with fewer chips than the
cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="also copy the profiler's .xplane.pb into this directory")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    src = os.path.join(harness.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"run.py: the program (src/repro) is not in this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    bench = harness.benchmark()
    metrics = harness.cell_metrics(bench, args.workload, bool(args.trace))
    ctx = harness.make_ctx(args.workload, args.seed, args.seconds, bool(args.trace), T_START)
    ctx.keep_trace = args.keep_trace
    harness.enable_compile_cache()
    try:
        res = harness.run_cell(ctx, metrics)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    harness.emit(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
