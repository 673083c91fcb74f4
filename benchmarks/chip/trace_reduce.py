"""Reduces a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Within the window named by a host annotation (``bench.window``):

* ``busy_s``: the union of the intervals in which an operation ran on the
  device (line "XLA Ops" of each ``/device:TPU:<n>`` plane), averaged over
  the devices;
* ``window_s``: the length of the window;
* ``ops``: device seconds per operation, named by the program it ran in
  and the HLO instruction, such as ``jit_train_step(145..)/fusion.514``;
  operations nest (a ``while`` covers its body), so these may add up to
  more than ``busy_s``;
* ``programs``: every compiled program run on the device (line "XLA
  Modules") that overlaps the window, in order, as ``[name, start_s,
  seconds]``: the device's clock can put the first program of the window
  a fraction of a millisecond before the host annotation opens;
* ``breakdown``: the ten operations that took most device time, and the ten
  longest idle gaps, each named by the host annotation that covers its
  middle (or "host" where none does).

Only ``jax.profiler.ProfileData`` is needed to read the file.
"""
from __future__ import annotations

import bisect
import collections
import gzip
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, w0: float, w1: float):
    a, b = max(a, w0), min(b, w1)
    return (a, b) if b > a else None


def reduce_events(planes: Dict[str, Dict[str, List[Tuple[str, float, float]]]],
                  window: str = "bench.window") -> Dict:
    """``planes``: plane name -> line name -> [(event name, start_ns,
    duration_ns)].  Separated from the file reader so that tests can feed
    hand-made events."""
    host = [(n, s, s + d) for p, lines in planes.items() if not DEVICE_PLANE.match(p)
            for evs in lines.values() for n, s, d in evs if n.startswith("bench.")]
    wins = [(s, e) for n, s, e in host if n == window]
    if not wins:
        raise ValueError(f"no host annotation {window!r} in the trace")
    w0, w1 = wins[0]
    devices = sorted(p for p in planes if DEVICE_PLANE.match(p))
    if not devices:
        raise ValueError("no /device:TPU:<n> plane in the trace")
    busy_total = 0.0
    ops: Dict[str, float] = collections.defaultdict(float)
    programs = []
    gaps = []
    for p in devices:
        iv = []
        mods = sorted(planes[p].get(MODULES_LINE, []), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        for n, s, d in planes[p].get(OPS_LINE, []):
            c = _clip(s, s + d, w0, w1)
            if c:
                iv.append(c)
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < mods[i][1] + mods[i][2]:
                    n = f"{mods[i][0]}/{n}"
                ops[n] += (c[1] - c[0]) * 1e-9
        u = union(iv)
        busy_total += sum(b - a for a, b in u)
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        if p == devices[0]:
            programs = [[n, (s - w0) * 1e-9, d * 1e-9]
                        for n, s, d in sorted(planes[p].get(MODULES_LINE, []), key=lambda e: e[1])
                        if s < w1 and s + d > w0]
    inner = [h for h in host if h[0] != window]

    def doing(a: float, b: float) -> str:
        mid = (a + b) / 2
        cover = [n for n, s, e in inner if s <= mid < e]
        return cover[-1] if cover else "host"

    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {"busy_s": busy_total * 1e-9 / len(devices), "window_s": (w1 - w0) * 1e-9,
            "devices": len(devices), "ops": dict(ops), "programs": programs,
            "breakdown": {"device_ops": [[n, t] for n, t in top_ops],
                          "idle_gaps": [[doing(a, b), (b - a) * 1e-9] for a, b in top_gaps]}}


def _short(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = bf16[..]
    fusion(..)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def read(path: str) -> Dict[str, Dict[str, List[Tuple[str, float, float]]]]:
    """The device and ``bench.`` host events of an ``.xplane.pb`` (or of
    one compressed as ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List]] = {}
    for plane in pd.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [(_short(ev.name) if dev else ev.name, float(ev.start_ns),
                    float(ev.duration_ns)) for ev in line.events
                   if dev or ev.name.startswith("bench.")]
            if evs:
                lines[line.name] = evs
        out[plane.name] = lines
    return out


def reduce(path: str, window: str = "bench.window") -> Dict:
    return reduce_events(read(path), window)
