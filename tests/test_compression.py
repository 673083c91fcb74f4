"""Gradient compression: quantization error bounds + error-feedback property
+ the shard_map all-reduce path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.compression import (dequantize_int8, ef_compress,
                                           ef_int8_psum, init_ef_state, quantize_int8)
from repro.launch.mesh import make_mesh


def test_quantization_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (512,)) * 3.0
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-6  # half-ULP symmetric rounding


@pytest.mark.parametrize("mag", [1e-8, 1e-3, 1.0, 1e3, 1e6])
def test_quantization_error_bound_across_magnitudes(mag):
    """The half-scale bound is scale-invariant: the quantizer normalizes by
    max|x|, so tiny and huge gradients round-trip with the same RELATIVE
    error -- err <= max|x| / 254."""
    x = jax.random.normal(jax.random.PRNGKey(1), (256,)) * mag
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    bound = float(np.abs(np.asarray(x)).max()) / 254.0
    assert err.max() <= bound * (1 + 1e-5)
    assert float(s) == pytest.approx(bound * 2, rel=1e-6)


def test_quantization_payload_is_really_int8():
    q, s = quantize_int8(jax.random.normal(jax.random.PRNGKey(2), (128,)) * 9.0)
    assert q.dtype == jnp.int8  # 4x fewer DCN bytes than f32, the whole point
    qn = np.asarray(q)
    assert qn.min() >= -127 and qn.max() <= 127  # symmetric, no -128
    assert qn.max() == 127 or qn.min() == -127  # max|x| maps to full scale


def test_quantization_of_zeros_is_exact():
    q, s = quantize_int8(jnp.zeros((32,)))
    np.testing.assert_array_equal(np.asarray(q), np.zeros(32, np.int8))
    np.testing.assert_array_equal(np.asarray(dequantize_int8(q, s)),
                                  np.zeros(32, np.float32))
    assert float(s) > 0  # the 1e-12 floor keeps x/scale finite


def test_ef_compress_conserves_signal_exactly():
    """EF bookkeeping identity: transmitted + carried == input + carry-in,
    to f32 roundoff -- nothing is ever lost, only delayed."""
    x = jax.random.normal(jax.random.PRNGKey(3), (128,)) * 0.3
    ef = jax.random.normal(jax.random.PRNGKey(4), (128,)) * 0.01
    q, s, new_ef = ef_compress(x, ef)
    sent = dequantize_int8(q, s)
    np.testing.assert_allclose(np.asarray(sent + new_ef), np.asarray(x + ef),
                               atol=1e-6)
    # and the carried error is itself bounded by the quantization step
    assert np.abs(np.asarray(new_ef)).max() <= float(s) / 2 + 1e-6


def test_error_feedback_unbiased_over_time():
    """EF: the accumulated transmitted signal converges to the true sum."""
    key = jax.random.PRNGKey(1)
    xs = jax.random.normal(key, (50, 256)) * 0.01  # small grads: worst case
    ef = jnp.zeros((256,), jnp.float32)
    sent = jnp.zeros((256,), jnp.float32)
    for i in range(50):
        q, s, ef = ef_compress(xs[i], ef)
        sent = sent + dequantize_int8(q, s)
    true = xs.sum(0)
    # residual error is bounded by the final carried error (not accumulated)
    np.testing.assert_allclose(np.asarray(sent + ef), np.asarray(true), atol=1e-4)


def test_shardmap_psum_single_device():
    mesh = make_mesh((1,), ("data",))
    grads = {"w": jnp.ones((8, 8)) * 0.5}
    ef = init_ef_state(grads)

    @jax.jit
    def run(g, e):
        return jax.shard_map(
            lambda g, e: ef_int8_psum(g, e, "data"), mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
            out_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        )(g, e)

    out, new_ef = run(grads, ef)
    np.testing.assert_allclose(np.asarray(out["w"]), 0.5 * np.ones((8, 8)), atol=0.01)
