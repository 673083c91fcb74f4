"""The harness finds cells, configurations and metrics by their file names,
refuses to run without a TPU, and refuses a device it has no peaks for."""
import json
import os
import shutil
import subprocess
import sys
import tempfile

import pytest

import harness

RUN = os.path.join(harness.CHIP, "run.py")


@pytest.fixture
def new_files():
    made = []

    def add(rel, text):
        path = os.path.join(harness.CHIP, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)
        made.append(path)

    yield add
    for p in made:
        os.remove(p)


def test_new_files_are_found_by_name(new_files):
    before = {p: os.path.getmtime(os.path.join(root, p))
              for root, _, files in os.walk(harness.CHIP) for p in files}
    cfg = dict(harness.load_json("configs", "bert-large.json"), num_hidden_layers=2)
    new_files("configs/zz-test-model.json", json.dumps(cfg))
    wl = dict(harness.load_json("workloads", "bert-large.vcycle.json"), config="zz-test-model")
    new_files("workloads/zz-test-model.vcycle.json", json.dumps(wl))
    new_files("metrics/zz_test_metric.train.py", "def read(ctx):\n    return 42.0\n")
    ctx = harness.make_ctx("zz-test-model.vcycle", 1, 1.0, True, 0.0)
    assert ctx.config["num_hidden_layers"] == 2
    assert ctx.workload["kind"] == "train"
    assert harness.load_module("metrics", "zz_test_metric.train.py").read(ctx) == 42.0
    bench = {"end_to_end": [{"name": "setup_s"}, {"name": "train_tokens_per_s"}],
             "per_layer": [{"name": "zz_test_metric.train", "moves": "train_tokens_per_s"}]}
    assert [m["name"] for m in harness.cell_metrics(bench, "zz-test-model.vcycle", True)] == \
        ["zz_test_metric.train"]
    after = {p: os.path.getmtime(os.path.join(root, p))
             for root, _, files in os.walk(harness.CHIP) for p in files if p in before}
    assert after == before


def test_metric_lists_follow_the_benchmark():
    bench = harness.benchmark()
    for w in bench["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(bench, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.cell_metrics(bench, w["name"], True)
        assert layer
        for m in layer:
            assert os.path.exists(os.path.join(harness.CHIP, "metrics", m["name"] + ".py"))
        assert os.path.exists(os.path.join(harness.CHIP, "workloads", w["name"] + ".json"))


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, RUN, "--workload", "bert-large.vcycle", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_needs_the_program():
    with tempfile.TemporaryDirectory() as d:
        shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), d)
        shutil.copytree(harness.CHIP, os.path.join(d, "benchmarks", "chip"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        p = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                            "bert-large.vcycle", "--seed", "1", "--seconds", "1",
                            "--trace", "0"], cwd=d, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_raises():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99")
