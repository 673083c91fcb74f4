"""What every cell shares: finding its files by name, the device, the compile
cache, counting compilations, the profiler trace, the per-layer readers, and
the result line.

A cell is ``workloads/<cell>.json``.  It names its configuration
(``configs/<config>.json``), its kind (``kinds/<kind>.py``, which exposes
``run(ctx) -> dict``) and its per-layer metrics (``metrics/<metric>.py``,
each exposing ``read(ctx) -> float | None``).  New cells, configurations and
metrics are new files; nothing here changes for them.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
from typing import Any, Dict, List, Optional

CHIP = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(CHIP))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> Dict:
    with open(os.path.join(CHIP, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """The module at ``benchmarks/chip/<parts>``, loaded once by its path
    (names such as ``metrics/step_ms.l0.train.py`` are not importable)."""
    name = "chipbench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(CHIP, *parts))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def benchmark() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones with trace off,
    per-layer ones with trace on (each listing its cells, or reported in
    every cell that reports the end-to-end metric it moves)."""
    def here(m):
        return cell in m.get("workloads", [cell])

    e2e = [m for m in bench["end_to_end"] if here(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)]


def enable_compile_cache() -> str:
    """JAX's persistent cache at one fixed directory inside the checkout, for
    every program however short its compile, with no size limit: with a
    limit, every write first reads an access-time file beside each entry,
    and one entry without it (written with no limit) fails every write."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


def device_info(chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX runs on {info['platform']!r}; there is no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return info


def memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


class Compiles:
    """Counts JAX's trace and compile events (``chip_smoke.py`` technique);
    a cache hit still traces, so a program new to the window shows here."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.n += 1


class Trace:
    """The profiler over the measured window.  ``reduce()`` turns the
    ``.xplane.pb`` into device busy time, per-kernel and per-program time
    (``trace_reduce.py``) and deletes the file."""

    def __init__(self, on: bool, keep: str = ""):
        self.on = on
        self.keep = keep
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-") if on else None
        self.summary = None

    def __enter__(self):
        if self.on:
            import jax

            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax

            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return False

    def reduce(self) -> Optional[Dict]:
        if not self.on:
            return None
        trace_reduce = load_module("trace_reduce.py")
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)[0]
            if self.keep:
                os.makedirs(self.keep, exist_ok=True)
                shutil.copy(path, self.keep)
            self.summary = trace_reduce.reduce(path, window="bench.window")
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.summary


def peaks_for(kind: str) -> Dict:
    table = load_json("peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; known: {sorted(table)}")
    return table[kind]


@dataclasses.dataclass
class Ctx:
    """One run of one cell: its files, arguments, and what the run gathers
    for the readers (counters from the kind module, the reduced trace)."""

    cell: str
    workload: Dict
    config: Dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    require_tpu: bool = True
    device: Dict = dataclasses.field(default_factory=dict)
    peaks: Dict = dataclasses.field(default_factory=dict)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    tracer: Optional[Trace] = None
    keep_trace: str = ""
    faults: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def log(self, msg: str) -> None:
        print(f"[{self.cell}] {msg}", file=sys.stderr, flush=True)


def make_ctx(cell: str, seed: int, seconds: float, trace: bool, t_start: float,
             workload: Optional[Dict] = None, config: Optional[Dict] = None,
             require_tpu: bool = True) -> Ctx:
    workload = workload or load_json("workloads", cell + ".json")
    config = config or load_json("configs", workload["config"] + ".json")
    return Ctx(cell, workload, config, seed, seconds, trace, t_start,
               require_tpu=require_tpu)


def run_cell(ctx: Ctx, metrics: List[Dict]) -> Dict:
    """Runs the cell's kind module and returns the result object; the module
    returns {"e2e": {...}, "compared": [(name, value, limit)], "attempted",
    "failed", "memory_peak_bytes"}."""
    ctx.device = device_info(ctx.workload["chips"], ctx.require_tpu)
    ctx.peaks = peaks_for(ctx.device["kind"]) if ctx.require_tpu else {}
    ctx.log(f"device {ctx.device}")
    kind = load_module("kinds", ctx.workload["kind"] + ".py")
    out = kind.run(ctx)
    device = dict(ctx.device, memory_peak_bytes=out["memory_peak_bytes"])
    values = dict(out["e2e"])
    if ctx.trace:
        s = ctx.tracer.summary
        names = collections.Counter(p[0] for p in s["programs"])
        ctx.log(f"trace: {s['devices']} device(s), programs in the window {dict(names)}")
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
        values = {}
        for m in metrics:
            v = load_module("metrics", m["name"] + ".py").read(ctx)
            if v is not None:
                values[m["name"]] = v
    res = {"correct": all(v <= lim for _, v, lim in out["compared"]),
           "attempted": out["attempted"], "failed": out["failed"],
           "metrics": {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                       for m in metrics if m["name"] in values},
           "device": device}
    if ctx.trace:
        res["breakdown"] = ctx.tracer.summary["breakdown"]
    res["compared"] = {n: {"value": v, "limit": lim} for n, v, lim in out["compared"]}
    return res


def emit(res: Dict) -> None:
    """The compared numbers beside their limits as the last lines on standard
    error, then the result as the last line on standard output."""
    for n, c in res["compared"].items():
        print(f"compared {n} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
