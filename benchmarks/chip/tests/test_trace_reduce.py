"""``trace_reduce``: busy/idle union, per-kernel and per-program time, on
hand-made events and on a trace recorded from one chip run."""
import os
import types

import pytest

import harness

tr = harness.load_module("trace_reduce.py")


def ev(name, start_us, dur_us):
    return (name, start_us * 1e3, dur_us * 1e3)


def hand_trace():
    host = {"python": [ev("bench.window", 100, 1000), ev("bench.tick", 300, 100),
                       ev("other.span", 300, 50)]}
    dev0 = {"XLA Ops": [ev("fusion.1", 50, 100),      # clipped to [100, 150]
                        ev("paged_attention_decode", 200, 50),
                        ev("fusion.2", 220, 60),        # overlaps the kernel
                        ev("fusion.1", 500, 100),
                        ev("fusion.3", 1050, 100)],     # clipped to [1050, 1100]
            "XLA Modules": [ev("jit_step(0)", 10, 30), ev("jit_step(1)", 40, 250),
                            ev("jit_step(2)", 490, 120), ev("jit_step(3)", 1050, 100),
                            ev("jit_step(4)", 1100, 50)],
            "Steps": [ev("0", 0, 2000)]}
    dev1 = {"XLA Ops": [ev("fusion.1", 100, 1000)]}
    return {"/host:CPU": host, "/device:TPU:0": dev0, "/device:TPU:1": dev1}


def test_union_and_clipping_by_hand():
    s = tr.reduce_events(hand_trace())
    assert s["window_s"] == pytest.approx(1000e-6)
    # device 0 busy: [100,150] + [200,280] + [500,600] + [1050,1100] = 280 us;
    # device 1 busy the whole window; the mean over the two devices
    assert s["busy_s"] == pytest.approx((280e-6 + 1000e-6) / 2)
    assert s["devices"] == 2
    # an operation is named by the program it runs in, where one covers it
    assert s["ops"]["jit_step(1)/paged_attention_decode"] == pytest.approx(50e-6)
    assert s["ops"]["jit_step(1)/fusion.1"] == pytest.approx(50e-6)
    assert s["ops"]["jit_step(2)/fusion.1"] == pytest.approx(100e-6)
    assert s["ops"]["fusion.1"] == pytest.approx(1000e-6)
    assert s["ops"]["jit_step(3)/fusion.3"] == pytest.approx(50e-6)
    # programs that overlap the window, on device 0, relative to its start
    assert [p[0] for p in s["programs"]] == ["jit_step(1)", "jit_step(2)", "jit_step(3)"]
    assert s["programs"][0][1:] == pytest.approx([-60e-6, 250e-6])
    assert s["programs"][1][1:] == pytest.approx([390e-6, 120e-6])
    # the longest idle gap of device 0, [600, 1050], is not under a bench span
    assert s["breakdown"]["idle_gaps"][0] == ["host", pytest.approx(450e-6)]
    # the gap [150, 200]... and [280, 500], whose middle lies in bench.tick
    assert ["bench.tick", pytest.approx(220e-6)] in s["breakdown"]["idle_gaps"]
    assert s["breakdown"]["device_ops"][0][0] == "fusion.1"  # device 1's, outside any program
    assert len(s["breakdown"]["device_ops"]) <= 10


def test_union():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_missing_window_or_device_raises():
    t = hand_trace()
    with pytest.raises(ValueError):
        tr.reduce_events(t, window="bench.nothing")
    del t["/device:TPU:0"], t["/device:TPU:1"]
    with pytest.raises(ValueError):
        tr.reduce_events(t)


# one TPU v5e run of bert-large.vcycle, `--seconds 1 --trace 1`: 11 train
# steps in the window, in the order of kinds/train.py's 31:15 schedule
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "bert-large.vcycle.1s.xplane.pb.gz")
LEVELS = [0, 0, 0, 1, 0, 0, 1, 0, 0, 1, 0]


def test_recorded_chip_trace():
    planes = tr.read(RECORDED)
    s = tr.reduce_events(planes)
    assert s["devices"] == 1
    (w0, w1), = [(a, a + d) for lines in planes.values() for evs in lines.values()
                 for n, a, d in evs if n == "bench.window"]
    assert s["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    # busy: a sweep over the clipped starts and ends of every operation,
    # counting time where at least one runs
    ops = planes["/device:TPU:0"]["XLA Ops"]
    edges = sorted(e for _, a, d in ops if a + d > w0 and a < w1
                   for e in ((max(a, w0), 1), (min(a + d, w1), -1)))
    busy, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    assert s["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0.99 < s["busy_s"] / s["window_s"] <= 1.0
    # per operation: the clipped durations of its events
    name = s["breakdown"]["device_ops"][0][0]
    prog, op = name.split("/")
    mods = [(a, a + d) for n, a, d in planes["/device:TPU:0"]["XLA Modules"] if n == prog]
    want = sum(min(a + d, w1) - max(a, w0) for n, a, d in ops
               if n == op and a + d > w0 and a < w1 and any(m0 <= a < m1 for m0, m1 in mods))
    assert s["ops"][name] == pytest.approx(want * 1e-9)
    assert " = " not in name
    # per program: one train-step program per dispatched step, the level-0
    # and level-1 programs told apart by their names
    progs = [p for p in s["programs"] if "train_step" in p[0]]
    assert len(progs) == len(LEVELS)
    names = {lv: {p[0] for p, l in zip(progs, LEVELS) if l == lv} for lv in (0, 1)}
    assert len(names[0]) == len(names[1]) == 1 and names[0] != names[1]
    steps = harness.load_module("metrics", "_train_steps.py")
    ctx = types.SimpleNamespace(counters={"levels": LEVELS},
                                tracer=types.SimpleNamespace(summary=s))
    assert steps.mean_ms(ctx, 0) == pytest.approx(154.82, rel=1e-3)
    assert steps.mean_ms(ctx, 1) == pytest.approx(25.54, rel=1e-3)
    with pytest.raises(ValueError):
        steps.mean_ms(types.SimpleNamespace(counters={"levels": LEVELS + [0]},
                                            tracer=ctx.tracer), 0)
