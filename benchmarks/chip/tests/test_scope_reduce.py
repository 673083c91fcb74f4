"""``scope_reduce``: device time per named scope and phase of the train
step, on hand-made events and on traces recorded from chip runs."""
import os
import types

import pytest

import harness

sr = harness.load_module("scope_reduce.py")
tr = harness.load_module("trace_reduce.py")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# a TPU v5e run of bert-large.vcycle, `--seconds 1 --trace 1`, of the program
# before it named its scopes and levels: 8 level-0 and 3 level-1 steps
UNSCOPED_TRACE = os.path.join(DATA, "bert-large.vcycle.1s.xplane.pb.gz")
L0_UNNAMED = "jit_train_step(14526107519590243401)"
L1_UNNAMED = "jit_train_step(15211009986104312367)"


@pytest.mark.parametrize("tf_op,want", [
    ("jit(train_step_l0)/jvp()/while/body/closed_call/attention/bse,ehd->bshd/dot_general:",
     ("attention", "forward")),
    ("jit(train_step_l0)/transpose(jvp())/while/body/closed_call/checkpoint/attention/"
     "bskgd,btkd->bkgst/dot_general:", ("attention", "backward")),
    ("jit(train_step_l0)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/bse,ef->bsf/dot_general:", ("mlp", "recompute")),
    ("jit(train_step_l0)/transpose(jvp(head))/bse,ve->bsv/dot_general:", ("head", "backward")),
    ("jit(train_step_l0)/jvp(loss)/bsv,bsv->bs/dot_general:", ("loss", "forward")),
    ("jit(train_step_l0)/transpose(jvp(embed))/transpose(jvp(jit(_take)))/scatter-add:",
     ("embed", "backward")),
    ("jit(train_step_l0)/optimizer/sub:", ("optimizer", "outside")),
    ("jit(train_step_l1)/jvp()/while/body/closed_call/moe/x:", ("moe", "forward")),
    ("jit(f)/head/loss/mul:", ("loss", "outside")),            # the innermost scope
    ("jit(f)/attention_like/mlps/mul:", ("unscoped", "outside")),  # whole components only
    ("jit(train_step)/transpose(jvp())/while/body/squeeze:", ("unscoped", "backward")),
    ("", ("unscoped", "outside")),
])
def test_classify_by_hand(tf_op, want):
    assert sr.classify(tf_op) == want


def ms(x):
    return x * 1e6  # ns


def hand_events():
    ops = [  # (tf_op, hlo_category, start_ns, duration_ns)
        ("jit(train_step_l0)/jvp()/while:", "while", ms(0), ms(6)),  # covers its body
        ("jit(train_step_l0)/jvp()/while/body/closed_call/attention/dot_general:",
         "convolution fusion", ms(0), ms(2)),
        ("jit(train_step_l0)/jvp()/while/body/closed_call/mlp/dot_general:",
         "convolution fusion", ms(2), ms(3)),
        ("jit(train_step_l0)/jvp(embed)/jit(_take)/gather:", "loop fusion", ms(5), ms(0.5)),
        ("jit(train_step_l0)/jvp(loss)/dot_general:", "loop fusion", ms(5.5), ms(0.5)),
        ("jit(train_step_l0)/transpose(jvp(head))/dot_general:", "convolution fusion",
         ms(6), ms(1)),
        ("jit(train_step_l0)/optimizer/sub:", "loop fusion", ms(7), ms(2)),
        ("", "non-fusion elementwise", ms(9), ms(1)),
        # the second level-0 step
        ("jit(train_step_l0)/jvp()/while/body/closed_call/attention/dot_general:",
         "convolution fusion", ms(20), ms(4)),
        ("jit(train_step_l0)/transpose(jvp())/while/body/closed_call/checkpoint/"
         "rematted_computation/mlp/dot_general:", "convolution fusion", ms(24), ms(2)),
        ("jit(train_step_l0)/optimizer/sub:", "loop fusion", ms(26), ms(2)),
        # level 1
        ("jit(train_step_l1)/jvp()/while/body/closed_call/attention/dot_general:",
         "convolution fusion", ms(40), ms(1)),
        # a step program outside the window, and another program in it
        ("jit(train_step_l0)/optimizer/sub:", "loop fusion", ms(101), ms(5)),
        ("jit(init)/add:", "loop fusion", ms(50), ms(3)),
    ]
    modules = [("jit_train_step_l0(7)", ms(0), ms(10)), ("jit_train_step_l0(7)", ms(20), ms(10)),
               ("jit_train_step_l1(8)", ms(40), ms(2)), ("jit_init(9)", ms(50), ms(3)),
               ("jit_train_step_l0(7)", ms(100), ms(10))]
    host = [("bench.window", ms(1), ms(60), {}),
            ("repro.train_step", ms(2), 5e3, {"level": 0}),
            ("repro.train_step", ms(3), 7e3, {"level": 0}),
            ("repro.train_step", ms(4), 3e3, {"level": 1}),
            ("repro.train_step", ms(70), 3e3, {"level": 0}),  # after the window
            ("other.span", ms(5), ms(1), {})]
    return {"ops": ops, "modules": modules, "host": host}


def test_reduce_by_hand():
    s = sr.reduce_events(hand_events())
    assert sorted(s["programs"]) == ["jit_train_step_l0(7)", "jit_train_step_l1(8)"]
    p0 = s["programs"]["jit_train_step_l0(7)"]
    assert p0["steps"] == 2 and p0["ms"] == pytest.approx(10.0)
    # leaf operations of both steps over two steps; the while is not a leaf
    assert p0["leaf_ms"] == pytest.approx((2 + 3 + 0.5 + 0.5 + 1 + 2 + 1 + 4 + 2 + 2) / 2)
    t = p0["table"]
    assert t["attention"] == pytest.approx({"forward": 3.0})
    assert t["mlp"] == pytest.approx({"forward": 1.5, "recompute": 1.0})
    assert t["head"] == pytest.approx({"backward": 0.5})
    assert t["embed"] == pytest.approx({"forward": 0.25})
    assert t["loss"] == pytest.approx({"forward": 0.25})
    assert t["optimizer"] == pytest.approx({"outside": 2.0})
    assert t["unscoped"] == pytest.approx({"outside": 0.5})
    assert s["spans"] == {"repro.train_step level=0": {"n": 2, "ms": pytest.approx(6e-3)},
                          "repro.train_step level=1": {"n": 1, "ms": pytest.approx(3e-3)}}
    got = sr.step_metrics(s, 0)
    assert got == pytest.approx({"attention_ms": 3.0, "mlp_ms": 2.5, "head_ms": 1.0,
                                 "optimizer_ms": 2.0, "recompute_ms": 1.0})
    assert "level=0" in sr.format_table(s)
    # a scope or phase with no operation is a fault, not a zero
    with pytest.raises(ValueError, match="moe"):
        sr.scope_ms(p0, "head", "moe")
    with pytest.raises(ValueError, match="mlp"):
        sr.step_metrics(s, 1)
    with pytest.raises(ValueError, match="recompute"):
        sr.phase_ms(s["programs"]["jit_train_step_l1(8)"], "recompute")
    with pytest.raises(ValueError, match="jit_train_step_l2"):
        sr.step_program(s, 2)
    with pytest.raises(ValueError):
        sr.reduce_events(hand_events(), window="bench.nothing")


def test_recorded_trace_before_scopes():
    """Without any scope, JAX's own name stack splits the level-0 step into
    its phases; the leaf operations add up to each program's duration."""
    s = sr.reduce(UNSCOPED_TRACE)
    assert {n: p["steps"] for n, p in s["programs"].items()} == {L0_UNNAMED: 8, L1_UNNAMED: 3}
    for p in s["programs"].values():
        assert p["leaf_ms"] == pytest.approx(p["ms"], rel=5e-3)
        assert set(p["table"]) == {"unscoped"}
    p0 = s["programs"][L0_UNNAMED]
    assert p0["ms"] == pytest.approx(154.82, rel=1e-3)
    assert sr.phase_ms(p0, "recompute") == pytest.approx(21.02, abs=0.05)
    assert sr.phase_ms(p0, "outside") == pytest.approx(19.01, abs=0.05)
    assert sr.phase_ms(p0, "forward") == pytest.approx(34.75, abs=0.05)
    assert sr.phase_ms(p0, "backward") == pytest.approx(80.00, abs=0.05)
    assert s["spans"] == {}
    with pytest.raises(ValueError):
        sr.step_program(s, 0)  # the program is not named by its level


def test_trace_reduce_unchanged_on_recorded_trace():
    """What the accepted metrics read from the recorded trace."""
    s = tr.reduce(UNSCOPED_TRACE)
    assert s["busy_s"] == pytest.approx(1.314390239, rel=1e-9)
    assert s["window_s"] == pytest.approx(1.317700808, rel=1e-9)
    assert len(s["ops"]) == 1517
    assert sum(s["ops"].values()) == pytest.approx(2.35948906, rel=1e-9)
    assert [p[0] for p in s["programs"]] == [
        L0_UNNAMED if lv == 0 else L1_UNNAMED for lv in (0, 0, 0, 1, 0, 0, 1, 0, 0, 1, 0)]
    assert s["programs"][0][1:] == pytest.approx([-0.000662557, 0.15481628], rel=1e-9)


# a TPU v5e run of bert-large.vcycle, `--seconds 1 --trace 1`, of the program
# with its named scopes, level-named step programs and host spans: 11 steps
# in the order of kinds/train.py's 31:15 schedule
SCOPED_TRACE = os.path.join(DATA, "bert-large.vcycle.scoped.1s.xplane.pb.gz")
LEVELS = [0, 0, 0, 1, 0, 0, 1, 0, 0, 1, 0]


def test_recorded_trace_with_scopes():
    s = sr.reduce(SCOPED_TRACE)
    l0, l1 = sr.step_program(s, 0), sr.step_program(s, 1)
    assert (l0["steps"], l1["steps"]) == (LEVELS.count(0), LEVELS.count(1))
    for p in (l0, l1):
        assert p["leaf_ms"] == pytest.approx(p["ms"], rel=5e-3)
        assert set(p["table"]) == {"attention", "mlp", "embed", "head", "loss", "optimizer",
                                   "unscoped"}
    # the five per-step readings of this run (ms of device time per step)
    assert sr.step_metrics(s, 0) == pytest.approx(
        {"attention_ms": 50.286, "mlp_ms": 64.157, "head_ms": 10.121, "optimizer_ms": 15.307,
         "recompute_ms": 21.017}, abs=1e-3)
    assert sr.step_metrics(s, 1) == pytest.approx(
        {"attention_ms": 7.311, "mlp_ms": 8.711, "head_ms": 5.520, "optimizer_ms": 2.011,
         "recompute_ms": 3.046}, abs=1e-3)
    # the same phases as the program before its scopes (test above)
    assert sr.phase_ms(l0, "recompute") == pytest.approx(21.02, abs=0.05)
    assert sr.phase_ms(l0, "outside") == pytest.approx(19.01, abs=0.05)
    # what no scope takes is the layer loop's own work (the scan's slicing and
    # its stacking of gradients) and operations XLA made without a name stack
    # (the weights' casts it hoists out of the loop): nothing inside a block
    # body and nothing under a named transformation
    unscoped = sum(l0["table"]["unscoped"].values())
    assert unscoped / l0["ms"] == pytest.approx(0.0964, abs=1e-3)
    ev = sr.read(SCOPED_TRACE)
    lost = {tf for tf, cat, _, _ in ev["ops"] if cat not in sr.CONTROL
            and sr.classify(tf)[0] == "unscoped"
            and ("closed_call" in tf or "jvp(" in tf.replace("jvp()", ""))}
    assert not lost, sorted(lost)[:5]
    # one host span per dispatched step, by level
    assert {k: v["n"] for k, v in s["spans"].items()} == {
        "repro.train_step level=0": LEVELS.count(0), "repro.train_step level=1": LEVELS.count(1)}


def test_step_programs_named_by_level_on_recorded_trace():
    """The accepted readers still pair programs with steps on the named
    trace, and each program's name now says its level."""
    s = tr.reduce(SCOPED_TRACE)
    progs = [p for p in s["programs"] if "train_step" in p[0]]
    assert [int(p[0].split("(")[0][-1]) for p in progs] == LEVELS
    steps = harness.load_module("metrics", "_train_steps.py")
    ctx = types.SimpleNamespace(counters={"levels": LEVELS},
                                tracer=types.SimpleNamespace(summary=s))
    assert steps.mean_ms(ctx, 0) == pytest.approx(154.834, rel=1e-4)
    assert steps.mean_ms(ctx, 1) == pytest.approx(25.537, rel=1e-4)
