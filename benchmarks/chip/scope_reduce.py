#!/usr/bin/env python3
"""Device time of each train step in a JAX profiler trace (``.xplane.pb``),
split by the program's named scopes and by the phase of the step.

    python3 benchmarks/chip/scope_reduce.py TRACE.xplane.pb[.gz]

A trace comes from ``run.py --trace 1 --keep-trace DIR``.  Each operation
on line "XLA Ops" of ``/device:TPU:0`` carries, in its metadata, the stat
``tf_op``: JAX's name stack, such as ``jit(train_step_l0)/transpose(jvp())/
while/body/closed_call/checkpoint/rematted_computation/attention/
dot_general:``.  ``jax.profiler.ProfileData`` does not expose those stats,
so the file is read here from its wire format with the standard library.

For every train-step program (an "XLA Modules" event whose name holds
``train_step``) that overlaps the host annotation ``bench.window``, the leaf
operations it ran (every operation but ``while``, ``conditional`` and
``call``, which cover their bodies) are summed

* by scope: the innermost of ``SCOPES`` that is a component of the name
  stack, bare (``attention``) or inside transformations (``jvp(head)``,
  ``transpose(jvp(loss))``); ``unscoped`` where there is none;
* by phase: ``recompute`` under JAX's ``rematted_computation``, else
  ``backward`` under ``transpose(``, else ``forward`` under ``jvp(``, else
  ``outside`` the gradient (the optimizer).

Programs are grouped by module name (``jit_train_step_l0(<id>)``) and each
number is device ms per step.  Host spans whose names start with ``repro.``
(``repro.train_step``, one per dispatched step) are counted per name and
argument, with their mean host duration.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import gzip
import re
import sys
from typing import Dict, Iterator, List, Tuple

SCOPES = ("attention", "ssm", "mlp", "moe", "embed", "head", "loss", "optimizer")
PHASES = ("forward", "recompute", "backward", "outside")
UNSCOPED = "unscoped"
CONTROL = ("while", "conditional", "call")
DEVICE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")

# ---------------------------------------------------------------------------
# the wire format (XSpace > XPlane > XLine > XEvent, tsl/profiler/protobuf/
# xplane.proto); field numbers:
#   XSpace.planes 1; XPlane.name 2, .lines 3, .event_metadata 4 (map),
#   .stat_metadata 5 (map); map entry key 1, value 2; XLine.name 2,
#   .timestamp_ns 3, .events 4; XEvent.metadata_id 1, .offset_ps 2,
#   .duration_ps 3, .stats 4; XEventMetadata.name 2, .stats 5;
#   XStatMetadata.name 2; XStat.metadata_id 1, int64 4, uint64 3, str 5,
#   ref_value 7 (the id of a stat metadata whose name is the string)


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varints, bytes for
    length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield key >> 3, v
        elif wire == 2:
            ln, i = _varint(b, i)
            yield key >> 3, b[i:i + ln]
            i += ln
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _stats(raw: List[bytes], stat_names: Dict[int, str]) -> Dict[str, object]:
    out = {}
    for s in raw:
        f = dict(_fields(s))
        if 7 in f:
            v = stat_names.get(f[7], "")
        elif 5 in f:
            v = f[5].decode(errors="replace")
        else:
            v = f.get(4, f.get(3))
        out[stat_names.get(f.get(1, 0), "")] = v
    return out


def _plane(b: bytes):
    name, lines, event_md, stat_md = "", [], {}, {}
    for f, v in _fields(b):
        if f == 2:
            name = v.decode()
        elif f == 3:
            lines.append(v)
        elif f in (4, 5):
            e = dict(_fields(v))
            (event_md if f == 4 else stat_md)[e.get(1, 0)] = e.get(2, b"")
    return name, lines, event_md, stat_md


def _events(line: bytes):
    """(line name, [(metadata id, start_ns, duration_ns, raw stats)])."""
    name, ts, evs = "", 0, []
    for f, v in _fields(line):
        if f == 2:
            name = v.decode()
        elif f == 3:
            ts = v
        elif f == 4:
            mid = off = dur = 0
            stats = []
            for g, x in _fields(v):
                if g == 1:
                    mid = x
                elif g == 2:
                    off = x
                elif g == 3:
                    dur = x
                elif g == 4:
                    stats.append(x)
            evs.append((mid, off, dur, stats))
    return name, [(m, ts + off * 1e-3, d * 1e-3, st) for m, off, d, st in evs]


def read(path: str) -> Dict[str, List]:
    """What ``reduce_events`` needs from an ``.xplane.pb`` (or ``.xplane.pb.gz``):
    ``ops`` [(tf_op, hlo_category, start_ns, duration_ns)] and ``modules``
    [(name, start_ns, duration_ns)] of ``/device:TPU:0``, and ``host``
    [(name, start_ns, duration_ns, {stat: value})] for the events of host
    planes named ``bench.*`` or ``repro.*``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = f.read()
    out = {"ops": [], "modules": [], "host": []}
    for field, pb in _fields(data):
        if field != 1:
            continue
        name, lines, event_md, stat_md = _plane(pb)
        device = name == DEVICE
        if not (device or name.startswith("/host:")):
            continue
        stat_names = {k: dict(_fields(v)).get(2, b"").decode() for k, v in stat_md.items()}
        meta = {}
        for k, v in event_md.items():
            md = collections.defaultdict(list)
            for g, x in _fields(v):
                md[g].append(x)
            meta[k] = ((md[2][0].decode(errors="replace") if md[2] else ""), md[5])
        for line in lines:
            lname, evs = _events(line)
            if device and lname == MODULES_LINE:
                out["modules"] += [(meta[m][0], s, d) for m, s, d, _ in evs]
            elif device and lname == OPS_LINE:
                cache: Dict[int, Tuple[str, str]] = {}
                for m, s, d, _ in evs:
                    if m not in cache:
                        st = _stats(meta[m][1], stat_names)
                        cache[m] = (str(st.get("tf_op", "")), str(st.get("hlo_category", "")))
                    out["ops"].append(cache[m] + (s, d))
            elif not device:
                for m, s, d, st in evs:
                    n = meta.get(m, ("", []))[0]
                    if n.startswith(("bench.", "repro.")):
                        out["host"].append((n, s, d, _stats(st, stat_names)))
    return out


# ---------------------------------------------------------------------------
# the reduction


def classify(tf_op: str) -> Tuple[str, str]:
    """(scope, phase) of one operation from its name stack (``tf_op`` is
    ``<name stack>:<op type>``)."""
    stack = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    scope, phase, parts = UNSCOPED, "outside", stack.split("/")
    for part in parts:
        while m := _WRAPPED.match(part):
            part = m.group(1)
        if part in SCOPES:
            scope = part
    if "rematted_computation" in parts:
        phase = "recompute"
    elif any(p.startswith("transpose(") for p in parts):
        phase = "backward"
    elif any(p.startswith("jvp(") for p in parts):
        phase = "forward"
    return scope, phase


def reduce_events(ev: Dict[str, List], window: str = "bench.window") -> Dict:
    """``ev`` as ``read`` returns it.  Separated from the reader so that tests
    can feed hand-made events."""
    wins = [(s, s + d) for n, s, d, _ in ev["host"] if n == window]
    if not wins:
        raise ValueError(f"no host annotation {window!r} in the trace")
    w0, w1 = wins[0]
    ops = sorted(ev["ops"], key=lambda o: o[2])
    starts = [o[2] for o in ops]
    progs = collections.defaultdict(lambda: {"steps": 0, "ms": 0.0, "leaf_ms": 0.0,
                                             "table": collections.defaultdict(
                                                 lambda: collections.defaultdict(float))})
    for name, s, d in sorted(ev["modules"], key=lambda m: m[1]):
        if "train_step" not in name or not (s < w1 and s + d > w0):
            continue
        p = progs[name]
        p["steps"] += 1
        p["ms"] += d * 1e-6
        for tf_op, cat, _, od in ops[bisect.bisect_left(starts, s):bisect.bisect_left(starts, s + d)]:
            if cat in CONTROL:
                continue
            scope, phase = classify(tf_op)
            p["table"][scope][phase] += od * 1e-6
            p["leaf_ms"] += od * 1e-6
    programs = {}
    for name, p in progs.items():
        n = p["steps"]
        programs[name] = {"steps": n, "ms": p["ms"] / n, "leaf_ms": p["leaf_ms"] / n,
                          "table": {sc: {ph: v / n for ph, v in row.items()}
                                    for sc, row in p["table"].items()}}
    spans = collections.defaultdict(list)
    for n, s, d, st in ev["host"]:
        if n.startswith("repro.") and w0 <= s < w1:
            key = " ".join([n] + [f"{k}={v}" for k, v in sorted(st.items())])
            spans[key].append(d * 1e-6)
    return {"programs": programs,
            "spans": {k: {"n": len(v), "ms": sum(v) / len(v)} for k, v in spans.items()}}


def reduce(path: str, window: str = "bench.window") -> Dict:
    return reduce_events(read(path), window)


# ---------------------------------------------------------------------------
# what per-layer metrics read


def step_program(summary: Dict, level: int) -> Dict:
    """The entry of the level's train-step program, ``jit_train_step_l<level>``."""
    pat = re.compile(rf"^jit_train_step_l{level}\b")
    got = [p for n, p in summary["programs"].items() if pat.match(n)]
    if len(got) != 1:
        raise ValueError(f"{len(got)} programs named jit_train_step_l{level} in the "
                         f"window; programs seen: {sorted(summary['programs'])}")
    return got[0]


def scope_ms(prog: Dict, *scopes: str) -> float:
    """Device ms per step under ``scopes``, every phase; raises where one of
    them has no operation, so that a lost scope shows as a fault."""
    missing = [s for s in scopes if s not in prog["table"]]
    if missing:
        raise ValueError(f"no operation under scope(s) {missing}; scopes seen: "
                         f"{sorted(prog['table'])}")
    return sum(sum(prog["table"][s].values()) for s in scopes)


def phase_ms(prog: Dict, phase: str) -> float:
    """Device ms per step in ``phase``, every scope; raises where none ran."""
    vals = [row[phase] for row in prog["table"].values() if phase in row]
    if not vals:
        raise ValueError(f"no operation in phase {phase!r}")
    return sum(vals)


def step_metrics(summary: Dict, level: int) -> Dict[str, float]:
    """Device ms per train step of ``level``: attention, MLP, the head
    (embedding, final norm and unembedding, loss), the optimizer, and the
    rematerialised forward across scopes."""
    p = step_program(summary, level)
    return {"attention_ms": scope_ms(p, "attention"), "mlp_ms": scope_ms(p, "mlp"),
            "head_ms": scope_ms(p, "embed", "head", "loss"),
            "optimizer_ms": scope_ms(p, "optimizer"), "recompute_ms": phase_ms(p, "recompute")}


def format_table(summary: Dict) -> str:
    """The scope x phase table of every train-step program, in ms per step."""
    out = []
    for name, p in sorted(summary["programs"].items()):
        t = p["table"]
        out.append(f"{name}: {p['steps']} steps, {p['ms']:.3f} ms per step, leaf "
                   f"operations {p['leaf_ms']:.3f} ms")
        out.append(f"  {'scope':<10}" + "".join(f"{ph:>11}" for ph in PHASES)
                   + f"{'total':>11}{'share':>8}")
        for sc in [s for s in SCOPES + (UNSCOPED,) if s in t]:
            tot = sum(t[sc].values())
            out.append(f"  {sc:<10}" + "".join(f"{t[sc].get(ph, 0.0):11.3f}" for ph in PHASES)
                       + f"{tot:11.3f}{100 * tot / p['leaf_ms']:7.2f}%")
        out.append(f"  {'all':<10}" + "".join(
            f"{sum(r.get(ph, 0.0) for r in t.values()):11.3f}" for ph in PHASES)
            + f"{p['leaf_ms']:11.3f}")
    for k, v in sorted(summary["spans"].items()):
        out.append(f"host span {k}: {v['n']} in the window, {v['ms']:.4f} ms each")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help=".xplane.pb or .xplane.pb.gz")
    args = ap.parse_args(argv)
    print(format_table(reduce(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
