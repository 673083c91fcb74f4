"""The trip-count-aware HLO cost parser vs known ground truths (and vs the
XLA limitation that motivated it)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze_text


def test_single_matmul_exact():
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    t = jax.jit(lambda w: w @ w).lower(w).compile().as_text()
    a = analyze_text(t)
    assert a["flops"] == pytest.approx(2 * 256 ** 3, rel=0.01)


def test_scan_multiplies_trip_count():
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def scanned(w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, w, None, length=7)
        return out

    compiled = jax.jit(scanned).lower(w).compile()
    a = analyze_text(compiled.as_text())
    assert a["flops"] == pytest.approx(7 * 2 * 128 ** 3, rel=0.01)
    # ...and document why this module exists: XLA counts the body once
    xla = compiled.cost_analysis()
    assert xla["flops"] < a["flops"] / 2


def test_nested_scan():
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def nested(w):
        def inner(c, _):
            return c @ w, None

        def outer(c, _):
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None

        out, _ = jax.lax.scan(outer, w, None, length=5)
        return out

    t = jax.jit(nested).lower(w).compile().as_text()
    a = analyze_text(t)
    assert a["flops"] == pytest.approx(15 * 2 * 64 ** 3, rel=0.01)


def test_dus_bytes_not_full_buffer():
    """In-place cache updates must count the slice, not the whole buffer."""
    big = jax.ShapeDtypeStruct((4096, 512), jnp.float32)
    upd = jax.ShapeDtypeStruct((1, 512), jnp.float32)

    def f(b, u):
        def body(c, i):
            return jax.lax.dynamic_update_slice(c, u, (i, 0)), None
        out, _ = jax.lax.scan(body, b, jnp.arange(100))
        return out

    t = jax.jit(f, donate_argnums=(0,)).lower(big, upd).compile().as_text()
    a = analyze_text(t)
    full = 100 * 4096 * 512 * 4
    assert a["bytes"] < full / 10  # slice-sized, not buffer-sized


def test_grad_flops_roughly_triple():
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)

    def loss(w, x):
        return jnp.sum((x @ w) ** 2)

    fwd = analyze_text(jax.jit(loss).lower(w, x).compile().as_text())["flops"]
    bwd = analyze_text(jax.jit(jax.grad(loss)).lower(w, x).compile().as_text())["flops"]
    assert 1.8 * fwd < bwd < 4.0 * fwd
