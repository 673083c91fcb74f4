"""Kernel dispatch subsystem: registry resolution, Pallas flash attention
forward AND backward parity (interpret mode), end-to-end ``attn_impl="pallas"``
execution, per-shard kernels under a mesh, and fused-vs-matrix equivalence of
the level-transition operators on a full parameter tree."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import tiny_dense
from repro.config import MultiLevelConfig
from repro.core import operators as ops
from repro.kernels import dispatch, ref
from repro.layers import attention as attn
from repro.models.api import build_model

ML = MultiLevelConfig(n_levels=2)


# ---------------------------------------------------------------------------
# registry / resolution


def test_registry_contents():
    assert dispatch.ops() == ("attention_tile", "coalesce_pair",
                              "flash_attention", "interp_axpy",
                              "paged_attention_decode")
    for op in dispatch.ops():
        assert dispatch.backends(op) == dispatch.BACKENDS


def test_resolution_order(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    assert dispatch.resolve_backend("interp_axpy") == dispatch.default_backend()
    monkeypatch.setenv(dispatch.ENV_VAR, "xla")
    assert dispatch.resolve_backend("interp_axpy") == "xla"
    # explicit argument beats the environment
    assert dispatch.resolve_backend("interp_axpy", "pallas-interpret") == "pallas-interpret"
    with pytest.raises(ValueError):
        dispatch.resolve_backend("interp_axpy", "cuda")
    with pytest.raises(KeyError):
        dispatch.resolve_backend("not_an_op", "xla")


@pytest.mark.skipif(jax.default_backend() == "tpu", reason="off-TPU behavior")
def test_pallas_downgrades_to_interpret_off_tpu():
    assert dispatch.resolve_backend("flash_attention", "pallas") == "pallas-interpret"
    assert dispatch.resolve_backend("paged_attention_decode", "pallas") == "pallas-interpret"


# ---------------------------------------------------------------------------
# paged_attention_decode: cross-backend agreement (xla gather oracle vs the
# Pallas kernel body in interpret mode)


def _paged_case(key=0, B=3, KH=2, G=2, D=16, N=12, P=8, M=3):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(ks[0], (B, KH, G, D), jnp.float32)
    k_pages = jax.random.normal(ks[1], (N, KH, P, D), jnp.float32)
    v_pages = jax.random.normal(ks[2], (N, KH, P, D), jnp.float32)
    # distinct pages per row; row 2 idle (length 0, table all null-page)
    bt = jnp.array([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    lengths = jnp.array([3 * P, P + 3, 0], jnp.int32)  # full / partial / idle
    return q, k_pages, v_pages, bt, lengths


def test_paged_attention_backends_agree():
    q, k_pages, v_pages, bt, lengths = _paged_case()
    got = {b: dispatch.dispatch("paged_attention_decode", q, k_pages, v_pages,
                                bt, lengths, backend=b)
           for b in ("xla", "pallas-interpret")}
    np.testing.assert_allclose(np.asarray(got["pallas-interpret"]),
                               np.asarray(got["xla"]), atol=1e-5, rtol=1e-5)
    # idle row (length 0) is exactly zero in BOTH backends -- the pinned
    # convention that keeps inactive decode slots backend-invariant
    for b, out in got.items():
        assert not np.asarray(out[2]).any(), f"{b}: idle row not zero"


def test_build_model_rejects_bad_backend():
    with pytest.raises(ValueError):
        build_model(tiny_dense(kernel_backend="cuda"))
    build_model(tiny_dense(kernel_backend="xla"))  # valid names pass


# ---------------------------------------------------------------------------
# Pallas flash attention fwd + bwd vs the naive oracle (interpret mode)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_vjp_grads_match_oracle(causal):
    B, H, S, D = 1, 2, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
    ct = jax.random.normal(ks[3], (B, H, S, D), jnp.float32)
    impl = dispatch.get_impl("flash_attention", "pallas-interpret")

    out = impl(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-3, rtol=1e-3)

    g_pl = jax.grad(lambda q, k, v: jnp.sum(
        impl(q, k, v, causal=causal, block_q=64, block_k=64) * ct),
        argnums=(0, 1, 2))(q, k, v)
    g_rf = jax.grad(lambda q, k, v: jnp.sum(
        ref.naive_attention(q, k, v, causal=causal) * ct),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pl, g_rf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# run_attention genuinely dispatches to the Pallas kernel


def _qkv(B=1, S=256, KH=2, G=2, D=16, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    q = jax.random.normal(ks[0], (B, S, KH, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KH, D), jnp.float32)
    ct = jax.random.normal(ks[3], (B, S, KH, G, D), jnp.float32)
    return q, k, v, ct


def test_run_attention_pallas_executes_kernel():
    calls = []
    orig = dispatch.get_impl("flash_attention", "pallas-interpret")
    dispatch.register("flash_attention", "pallas-interpret",
                      lambda *a, **kw: (calls.append(1), orig(*a, **kw))[1],
                      override=True)
    try:
        cfg = tiny_dense(attn_impl="pallas", attn_block_k=64)
        q, k, v, _ = _qkv()
        attn.run_attention(q, k, v, cfg, causal=True, scale=q.shape[-1] ** -0.5)
    finally:
        dispatch.register("flash_attention", "pallas-interpret", orig, override=True)
    assert calls, "attn_impl='pallas' did not reach the Pallas kernel"


def test_run_attention_pallas_grads_match_xla_flash():
    """Acceptance gate: pallas fwd+bwd vs the flash_xla path, <= 1e-3."""
    D = 16
    cfg_p = tiny_dense(attn_impl="pallas", attn_block_k=64)
    cfg_b = cfg_p.replace(attn_impl="blockwise")
    q, k, v, ct = _qkv(D=D)

    def loss(cfg):
        return lambda q, k, v: jnp.sum(
            attn.run_attention(q, k, v, cfg, causal=True, scale=D ** -0.5) * ct)

    o_p = attn.run_attention(q, k, v, cfg_p, causal=True, scale=D ** -0.5)
    o_b = attn.run_attention(q, k, v, cfg_b, causal=True, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_b), atol=1e-3)
    g_p = jax.grad(loss(cfg_p), argnums=(0, 1, 2))(q, k, v)
    g_b = jax.grad(loss(cfg_b), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_p, g_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_run_attention_pallas_fallback_on_untileable():
    """Shapes the tiling cannot cover (causal S != T) keep the XLA flash path
    rather than erroring."""
    cfg = tiny_dense(attn_impl="pallas", attn_block_k=64)
    B, S, T, KH, G, D = 1, 192, 256, 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, KH, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KH, D), jnp.float32)
    out = attn.run_attention(q, k, v, cfg, causal=True, scale=D ** -0.5)
    assert out.shape == (B, S, KH, G, D)


def test_run_attention_xla_backend_override():
    """kernel_backend='xla' pins the flash_xla path even under attn_impl='pallas'."""
    calls = []
    orig = dispatch.get_impl("flash_attention", "pallas-interpret")
    dispatch.register("flash_attention", "pallas-interpret",
                      lambda *a, **kw: (calls.append(1), orig(*a, **kw))[1],
                      override=True)
    try:
        cfg = tiny_dense(attn_impl="pallas", attn_block_k=64, kernel_backend="xla")
        q, k, v, _ = _qkv()
        attn.run_attention(q, k, v, cfg, causal=True, scale=q.shape[-1] ** -0.5)
    finally:
        dispatch.register("flash_attention", "pallas-interpret", orig, override=True)
    assert not calls


# ---------------------------------------------------------------------------
# fused (matrix-free) vs dense-matrix level transitions on a full model tree


def _tree_err(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _tinyllama_proxy():
    """The tinyllama-1.1b architecture at smoke width (same stage/leaf
    structure and axis roles; widths shrunk so CPU tests stay fast)."""
    from repro.configs.tinyllama_1_1b import smoke

    return smoke()


def test_fused_coalesce_matches_matrix_on_tinyllama():
    cfg = _tinyllama_proxy()
    model = build_model(cfg)
    specs = model.specs()
    params = model.init(jax.random.PRNGKey(0))
    fused = ops.make_coalesce_fn(specs, cfg, ML)(params)
    dense = ops.make_coalesce_fn(specs, cfg, ML, fused=False)(params)
    assert _tree_err(fused, dense) <= 1e-5


def test_fused_decoalesce_interpolate_match_matrix_on_tinyllama():
    cfg = _tinyllama_proxy()
    model = build_model(cfg)
    specs = model.specs()
    small = build_model(ops.coalesce_config(cfg, ML))
    p_small = small.init(jax.random.PRNGKey(1))
    de_f = ops.make_decoalesce_fn(specs, cfg, ML)(p_small)
    de_m = ops.make_decoalesce_fn(specs, cfg, ML, fused=False)(p_small)
    assert _tree_err(de_f, de_m) <= 1e-5
    p_large = model.init(jax.random.PRNGKey(2))
    mixed = ops.make_interpolate_fn(0.25)(p_large, de_f)
    want = jax.tree.map(lambda a, b: 0.75 * a + 0.25 * b, p_large, de_m)
    assert _tree_err(mixed, want) <= 1e-5


def test_fused_cd_identity_pallas_interpret(monkeypatch):
    """C(D(w)) == id with every stack leaf routed through the interpreted
    Pallas kernels end to end (the CPU validation backend)."""
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas-interpret")
    cfg = tiny_dense(compute_dtype=jnp.float32)
    model = build_model(cfg)
    specs = model.specs()
    small = build_model(ops.coalesce_config(cfg, ML))
    p_small = small.init(jax.random.PRNGKey(3))
    de = ops.make_decoalesce_fn(specs, cfg, ML)(p_small)
    rt = ops.make_coalesce_fn(specs, cfg, ML)(de)
    assert _tree_err(rt, p_small) <= 1e-5


def test_coalesce_pair_degenerate_dims_fall_back_to_xla(monkeypatch):
    """Odd/prime dims used to leave no aligned tile and fall back to XLA.
    They no longer do: the Pallas kernel pads them (each half of the paired
    dim, the tail of the other) and stays correct, so a ``pallas`` op always
    runs its kernel."""
    calls = []
    orig = dispatch.coalesce_pair_xla
    monkeypatch.setattr(dispatch, "coalesce_pair_xla",
                        lambda *a, **kw: (calls.append(1), orig(*a, **kw))[1])
    w = jax.random.normal(jax.random.PRNGKey(4), (514, 6), jnp.float32)  # 257 prime
    got = dispatch.dispatch("coalesce_pair", w, axis=0, w0=0.5,
                            backend="pallas-interpret")
    want = ref.coalesce_pair_ref(w, axis=0, w0=0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # prime non-projected dim, padded at its tail
    w2 = jax.random.normal(jax.random.PRNGKey(5), (257, 8), jnp.float32)
    got2 = dispatch.dispatch("coalesce_pair", w2, axis=1, w0=1.0,
                             backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(got2),
                               np.asarray(ref.coalesce_pair_ref(w2, axis=1, w0=1.0)),
                               atol=1e-5)
    # a paired half with no aligned divisor above the block: (2*600, 130)
    w3 = jax.random.normal(jax.random.PRNGKey(6), (1200, 130), jnp.float32)
    got3 = dispatch.dispatch("coalesce_pair", w3, axis=0, w0=0.5, block=128,
                             backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(got3),
                               np.asarray(ref.coalesce_pair_ref(w3, axis=0, w0=0.5)),
                               atol=1e-5)
    assert not calls


def test_kernels_run_per_shard_under_a_mesh():
    """Under ``mesh_ctx`` every Pallas op runs per shard in ``shard_map``
    (XLA cannot partition a Mosaic kernel): on a 2x2 mesh of host devices,
    with sharded inputs inside jit, each op (flash fwd and grads too) equals
    the unsharded kernel.  Subprocess: this process keeps one CPU device."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import mesh_ctx
        from repro.kernels import dispatch
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 2), ("data", "model"))
        rules = {"cache_kv_heads": "model"}
        ks = iter(jax.random.split(jax.random.PRNGKey(0), 12))
        rnd = lambda *s: jax.random.normal(next(ks), s, jnp.float32)
        q, k, v = rnd(2, 4, 256, 16), rnd(2, 4, 256, 16), rnd(2, 4, 256, 16)
        bt = jnp.array([[1, 2, 3], [4, 5, 0]], jnp.int32)
        cases = {
            "flash_attention": (lambda q, k, v: jax.value_and_grad(
                lambda q, k, v: jnp.sum(dispatch.dispatch(
                    "flash_attention", q, k, v, causal=True, block_q=64,
                    block_k=64, backend="pallas-interpret") ** 2),
                argnums=(0, 1, 2))(q, k, v), (q, k, v)),
            "coalesce_pair": (lambda w: dispatch.dispatch(
                "coalesce_pair", w, axis=0, w0=0.5,
                backend="pallas-interpret"), (rnd(24, 256),)),
            "interp_axpy": (lambda a, b: dispatch.dispatch(
                "interp_axpy", a, b, 0.25, backend="pallas-interpret"),
                (rnd(8, 96), rnd(8, 96))),
            "paged_attention_decode": (lambda q, kp, vp: dispatch.dispatch(
                "paged_attention_decode", q, kp, vp, bt,
                jnp.array([20, 11], jnp.int32), backend="pallas-interpret"),
                (rnd(2, 4, 2, 16), rnd(6, 4, 8, 16), rnd(6, 4, 8, 16))),
        }
        for op, (fn, args) in cases.items():
            want = jax.jit(fn)(*args)
            placed = [jax.device_put(a, NamedSharding(mesh, P(*(
                ("data",) if a.shape[0] % 2 == 0 else (None,)))))
                      for a in args]
            with mesh_ctx(mesh, rules):
                got = jax.jit(fn)(*placed)
                assert "shard_map" in str(jax.make_jaxpr(fn)(*placed)), op
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           atol=1e-5, rtol=1e-5, err_msg=op)
        print("PER_SHARD_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src" + os.pathsep + "tests",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PER_SHARD_OK" in out.stdout
