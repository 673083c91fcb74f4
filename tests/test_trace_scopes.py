"""Names in the train step's trace: each level's program is named
``jit_train_step_l<level>``, its work sits under the named scopes that the
benchmark's ``scope_reduce.py`` reads, and the host span around each
dispatch passes arguments, donation and outputs through unchanged."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import tiny_dense, tiny_hybrid, tiny_moe
from repro.config import MultiLevelConfig, TrainConfig
from repro.configs.paper_models import bert_proxy
from repro.core.vcycle import VCycleRunner
from repro.models.api import build_model, make_train_step
from repro.optim import adamw_init

STEP_SCOPES = {"attention", "mlp", "head", "loss", "embed", "optimizer"}
OP_NAME = re.compile(r'op_name="([^"]*)"')
WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def components(op_name):
    """The name stack's components with transformations taken off:
    ``transpose(jvp(head))`` -> ``head``."""
    out = []
    for part in op_name.split("/"):
        while m := WRAPPED.match(part):
            part = m.group(1)
        out.append(part)
    return out


def dot_op_names(hlo_text):
    return [OP_NAME.search(line).group(1) for line in hlo_text.splitlines()
            if OP_NAME.search(line) and OP_NAME.search(line).group(1).endswith("dot_general")]


@pytest.fixture(scope="module")
def setup():
    cfg = bert_proxy(d_model=32, n_layers=2, vocab=64).replace(remat="full")
    tc = TrainConfig(steps=10, batch_size=2, seq_len=8, peak_lr=1e-3, warmup_steps=1)
    runner = VCycleRunner(cfg, MultiLevelConfig(n_levels=2, alpha=0.5), tc,
                          batch_fn=lambda g: None)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 64, size=(2, 8), dtype=np.int32)
    labels = np.where(rng.random((2, 8)) < 0.5, toks, -1).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    return runner, tc, batch


def _state(runner, tc, level):
    params = runner.models[level].init(jax.random.PRNGKey(level))
    return params, adamw_init(params, tc)


@pytest.mark.parametrize("level", [0, 1])
def test_step_program_named_by_level_and_scoped(setup, level):
    runner, tc, batch = setup
    fn = runner.step_fn(level)
    assert fn.__name__ == f"train_step_l{level}"
    p, o = jax.eval_shape(lambda: _state(runner, tc, level))
    text = fn.__wrapped__.lower(p, o, batch).compile().as_text()
    assert re.search(rf"^HloModule jit_train_step_l{level}\b", text, re.M)
    names = dot_op_names(text)
    assert names
    outside = [n for n in names if not STEP_SCOPES & set(components(n))]
    assert not outside, outside
    # the layer loop's forward, backward and recompute each name both sub-layers
    assert any("rematted_computation/attention/" in n for n in names)
    assert any("rematted_computation/mlp/" in n for n in names)
    assert any(n.startswith(f"jit(train_step_l{level})/transpose(jvp(head))") for n in names)


@pytest.mark.parametrize("level", [0, 1])
def test_span_passes_step_through_unchanged(setup, level, monkeypatch):
    """The step from ``step_fn`` returns bit for bit what an unscoped,
    unnamed ``jax.jit(make_train_step(...))`` returns, and still donates its
    parameters and optimizer state."""
    runner, tc, batch = setup
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = jax.jit(make_train_step(runner.models[level], tc))
        p, o = _state(runner, tc, level)
        assert "/attention/" not in plain.lower(p, o, batch).as_text(debug_info=True)
        want_p, want_o, want_m = plain(p, o, batch)
    p, o = _state(runner, tc, level)
    got_p, got_o, got_m = runner.step_fn(level)(p, o, batch)
    assert all(x.is_deleted() for x in jax.tree.leaves((p, o)))
    assert float(got_m["loss"]) == float(want_m["loss"])
    for a, b in zip(jax.tree.leaves((got_p, got_o)), jax.tree.leaves((want_p, want_o))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("cfg,scopes", [
    (tiny_dense(), {"attention", "mlp"}),
    (tiny_moe(), {"attention", "mlp", "moe"}),
    (tiny_hybrid(), {"ssm", "attention", "mlp"}),
], ids=["dense", "moe", "hybrid"])
def test_block_scopes_by_family(cfg, scopes):
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32), "labels": jnp.zeros((2, 8), jnp.int32)}
    text = jax.jit(model.loss).lower(params, batch).as_text(debug_info=True)
    seen = {c for n in re.findall(r'loc\("([^"]*)"', text) for c in components(n)}
    assert scopes | {"embed", "head", "loss"} <= seen
