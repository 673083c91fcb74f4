"""Compile-only checks of the Pallas kernels for a TPU v5e chip that is
described, not attached: Mosaic refuses what interpret mode accepts (block
shapes off the (8, 128) tiling, too much VMEM), so each kernel of the main
path is compiled here at GPT-Base widths and must lower to a real Mosaic
kernel (``tpu_custom_call``).  Nothing runs; these say nothing about results
or speed.

The topology is described inside a module fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch

# GPT-Base (configs/paper_models.py): d_model 768, 12 heads of 64, d_ff 3072,
# vocab 50257 padded to 50304, 12 layers; training batch 8 x 1024 tokens
E, H, D, F, V, L = 768, 12, 64, 3072, 50304, 12
B, S = 8, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text, n=1):
    assert text.count("tpu_custom_call") >= n, "no Mosaic kernel in the program"


@pytest.mark.parametrize("shape", [(V, E), (L, E, F), (L, E), (L, H, D)],
                         ids=["embed", "mlp", "bias", "qkv_bias"])
def test_interp_axpy_compiles(one_chip, shape):
    fn = dispatch.get_impl("interp_axpy", "pallas")
    text = _compile_text(lambda a, b: fn(a, b, 0.25), one_chip,
                         (shape, jnp.float32), (shape, jnp.float32))
    _assert_kernel(text)


# the V-cycle folds each leaf to [n, rest] with the coalesced axis first:
# the tied embedding's d_model (768, 50304), attention (768, 12*12*64), the
# MLP's d_ff (3072, 12*768), and the level-1 (half-width) embedding
@pytest.mark.parametrize("shape", [(E, V), (E, L * H * D), (F, L * E),
                                   (E // 2, V)],
                         ids=["embed", "attn", "mlp", "embed_level1"])
def test_coalesce_pair_compiles(one_chip, shape):
    fn = dispatch.get_impl("coalesce_pair", "pallas")
    text = _compile_text(lambda w: fn(w, axis=0, w0=0.5), one_chip,
                         (shape, jnp.float32))
    _assert_kernel(text)


@pytest.mark.parametrize("heads", [H, H // 2], ids=["level0", "level1"])
def test_flash_attention_fwd_and_vjp_compile(one_chip, heads):
    fn = dispatch.get_impl("flash_attention", "pallas")
    qkv = [((B, heads, S, D), jnp.bfloat16)] * 3

    def fwd(q, k, v):
        return fn(q, k, v, causal=True, block_q=128, block_k=128)

    _assert_kernel(_compile_text(fwd, one_chip, *qkv))

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    # forward (lse-emitting) + dq kernel + dk/dv kernel
    _assert_kernel(_compile_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                                 *qkv), n=3)


def test_paged_attention_decode_compiles(one_chip):
    fn = dispatch.get_impl("paged_attention_decode", "pallas")
    batch, page, max_seq = 4, 16, 1024
    M = max_seq // page
    N = batch * M + 1
    text = _compile_text(
        fn, one_chip,
        ((batch, H, 1, D), jnp.bfloat16),      # q [B, KH, G, D]
        ((N, H, page, D), jnp.bfloat16),       # k pool [N, KH, P, D]
        ((N, H, page, D), jnp.bfloat16),       # v pool
        ((batch, M), jnp.int32),               # block tables
        ((batch,), jnp.int32))                 # lengths
    _assert_kernel(text)
