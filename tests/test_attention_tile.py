"""``attention_tile``: rotary + single-tile attention in one Pallas kernel
pair, run here by the Pallas interpreter against its ``xla`` reference
(``apply_rope`` + ``plain_attention``), and the rule by which ``gqa_apply``
takes it."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import bert_proxy
from repro.kernels import dispatch
from repro.layers import attention as attn
from repro.models.api import build_model

THETA = 10000.0


def _operands(B, S, H, D, dtype, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    q, k, v, do = (jax.random.normal(kk, (B, S, H * D), jnp.float32).astype(dtype)
                   for kk in ks)
    # each row at its own offset: the rotary reads the positions
    positions = jnp.arange(S)[None] + jnp.array([[0], [37]])[:B]
    return q, k, v, do, positions


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, (1e-2, 2e-2)),
                                       (jnp.float32, (1e-5, 1e-5))],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
@pytest.mark.parametrize("H,D", [(2, 64), (4, 32)], ids=["2x64", "4x32"])
@pytest.mark.parametrize("S", [64, 128])
def test_values_and_grads_match_reference(S, H, D, causal, dtype, tol):
    q, k, v, do, positions = _operands(2, S, H, D, dtype)
    kw = dict(head_dim=D, causal=causal, scale=D ** -0.5, theta=THETA)
    kernel = dispatch.get_impl("attention_tile", "pallas-interpret")
    ref = dispatch.get_impl("attention_tile", "xla")
    o, vjp = jax.vjp(lambda *a: kernel(*a, positions, **kw), q, k, v)
    o_ref, vjp_ref = jax.vjp(lambda *a: ref(*a, positions, **kw), q, k, v)
    assert o.dtype == dtype and o.shape == q.shape
    assert _rel(o, o_ref) <= tol[0]
    for name, g, g_ref in zip("qkv", vjp(do), vjp_ref(do)):
        assert g.dtype == dtype
        assert _rel(g, g_ref) <= tol[1], name


def test_three_rows_in_one_grid_step():
    """A batch of three one-group rows runs as one grid step of three rows,
    each with its own positions."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v, do = (jax.random.normal(kk, (3, 64, 128), jnp.float32) for kk in ks)
    positions = jnp.arange(64)[None] + jnp.array([[0], [5], [90]])
    kw = dict(head_dim=64, causal=True, scale=0.125, theta=THETA)
    kernel = dispatch.get_impl("attention_tile", "pallas-interpret")
    ref = dispatch.get_impl("attention_tile", "xla")
    o, vjp = jax.vjp(lambda *a: kernel(*a, positions, **kw), q, k, v)
    o_ref, vjp_ref = jax.vjp(lambda *a: ref(*a, positions, **kw), q, k, v)
    assert _rel(o, o_ref) <= 1e-5
    for g, g_ref in zip(vjp(do), vjp_ref(do)):
        assert _rel(g, g_ref) <= 1e-5


def test_causal_row_sees_only_its_prefix():
    """A change to the last key moves only the last query's output."""
    q, k, v, _, positions = _operands(2, 64, 2, 64, jnp.float32)
    run = dispatch.get_impl("attention_tile", "pallas-interpret")
    kw = dict(head_dim=64, causal=True, scale=0.125, theta=THETA)
    a = run(q, k, v, positions, **kw)
    b = run(q, k.at[:, -1].add(1.0), v.at[:, -1].add(1.0), positions, **kw)
    np.testing.assert_array_equal(np.asarray(a[:, :-1]), np.asarray(b[:, :-1]))
    assert not np.allclose(np.asarray(a[:, -1]), np.asarray(b[:, -1]))


def test_runs_per_shard_under_a_mesh():
    """Under ``mesh_ctx`` the kernels run per shard: rows split over the
    data axis and the merged heads over the model axis where each shard
    keeps whole 128-lane tiles (2x2: two heads of 64 each), else the heads
    stay whole (1x4).  Values and gradients equal the unsharded kernel's.
    Subprocess: this process keeps one CPU device."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import mesh_ctx
        from repro.kernels import dispatch
        from repro.launch.mesh import make_mesh

        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (4, 32, 256)) for kk in ks)
        pos = jnp.broadcast_to(jnp.arange(32)[None], (4, 32))

        def fn(q, k, v):
            return jax.value_and_grad(lambda q, k, v: jnp.sum(dispatch.dispatch(
                "attention_tile", q, k, v, pos, head_dim=64, causal=True,
                scale=0.125, theta=10000.0, backend="pallas-interpret") ** 2),
                argnums=(0, 1, 2))(q, k, v)

        want = jax.jit(fn)(q, k, v)
        for shape in [(2, 2), (1, 4)]:
            mesh = make_mesh(shape, ("data", "model"))
            placed = [jax.device_put(a, NamedSharding(mesh, P("data")))
                      for a in (q, k, v)]
            with mesh_ctx(mesh):
                got = jax.jit(fn)(*placed)
                assert "shard_map" in str(jax.make_jaxpr(fn)(*placed))
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           atol=1e-5, rtol=1e-5)
        print("PER_SHARD_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PER_SHARD_OK" in out.stdout


# ---------------------------------------------------------------------------
# when gqa_apply takes the kernel


def _cfg(d_model=256, heads=4, **kw):
    return bert_proxy(d_model=d_model, n_layers=2, vocab=64).replace(
        n_heads=heads, n_kv_heads=heads, **kw)


def test_engages_for_short_uncached_self_attention_on_tpu(monkeypatch):
    """With nothing pinned, a TPU resolves the op to its Mosaic kernel."""
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    assert attn._tile_backend(_cfg(), 128, use_rope=True, cache=None) == "pallas"


@pytest.mark.parametrize("case", [
    dict(cache={}), dict(S=197), dict(S=1024), dict(use_rope=False),
    dict(cfg=_cfg(d_model=96, heads=4)), dict(cfg=_cfg(kernel_backend="xla")),
    dict(S=100),
], ids=["cache", "S197", "S1024", "no_rope", "lanes", "xla", "S_not_8k"])
def test_keeps_the_old_path(case, monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    got = attn._tile_backend(case.get("cfg", _cfg()), case.get("S", 128),
                             use_rope=case.get("use_rope", True),
                             cache=case.get("cache"))
    assert got is None


def test_cpu_default_keeps_the_old_path(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    if dispatch.on_tpu():
        pytest.skip("off-TPU behaviour")
    assert attn._tile_backend(_cfg(), 128, use_rope=True, cache=None) is None


def _loss_and_grads(cfg, batch, monkeypatch, backend):
    monkeypatch.setenv(dispatch.ENV_VAR, backend)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dispatch.reset_traced()
    (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
    return float(loss), grads, dispatch.traced()


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
def test_train_step_matches_xla_path(kv_heads, monkeypatch):
    """A bert_proxy loss and its gradients through the interpreted kernel
    agree with the unchanged XLA path within bfloat16 rounding."""
    cfg = bert_proxy(d_model=128, n_layers=2, vocab=64).replace(
        n_kv_heads=kv_heads, remat="full", compute_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 64, size=(2, 32), dtype=np.int32)
    labels = np.where(rng.random((2, 32)) < 0.5, toks, -1).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    loss, grads, traced = _loss_and_grads(cfg, batch, monkeypatch, "pallas-interpret")
    assert traced.get(("attention_tile", "pallas-interpret"), 0) >= 1
    want, want_grads, traced = _loss_and_grads(cfg, batch, monkeypatch, "xla")
    assert not any(op == "attention_tile" for op, _ in traced)
    assert abs(loss - want) <= 1e-3 * abs(want)
    flat = lambda t: np.concatenate([np.ravel(np.asarray(x, np.float32))
                                     for x in jax.tree.leaves(t)])
    g, w = flat(grads), flat(want_grads)
    assert np.linalg.norm(g - w) <= 1e-2 * np.linalg.norm(w)
