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
from repro.layers.attention import FLASH_TILE

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


def _assert_flash_compiles(one_chip, heads, *, causal, block):
    """The forward, and the gradient's forward + dq + dk/dv, each a named
    Mosaic kernel."""
    import re

    fn = dispatch.get_impl("flash_attention", "pallas")
    qkv = [((B, heads, S, D), jnp.bfloat16)] * 3

    def fwd(q, k, v):
        return fn(q, k, v, causal=causal, block_q=block, block_k=block)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    kernels = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")
    for f, names in ((fwd, kernels[:1]), (jax.grad(loss, argnums=(0, 1, 2)), kernels)):
        text = _compile_text(f, one_chip, *qkv)
        for kernel in names:
            assert re.search(rf"%[\w.]*{kernel}[\w.]* = .*tpu_custom_call", text), kernel


# the tiles the program takes (FLASH_TILE: the causal diagonal tile is walked
# in sub-blocks)
@pytest.mark.parametrize("heads", [H, H // 2], ids=["level0", "level1"])
def test_flash_attention_fwd_and_vjp_compile(one_chip, heads):
    _assert_flash_compiles(one_chip, heads, causal=True, block=FLASH_TILE)


# tiles computed in one piece: causal 128-tiles, bidirectional whole tiles
@pytest.mark.parametrize("causal,block", [(True, 128), (False, FLASH_TILE)],
                         ids=["causal_128", "bidir"])
@pytest.mark.parametrize("heads", [H, H // 2], ids=["level0", "level1"])
def test_flash_attention_whole_tiles_compile(one_chip, heads, causal, block):
    _assert_flash_compiles(one_chip, heads, causal=causal, block=block)


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


# BERT-Large training (benchmarks/chip/configs/bert-large.json): 64 x 128
# tokens, 16 heads of 64 at level 0, 8 at level 1 of the V-cycle
@pytest.mark.parametrize("heads", [16, 8], ids=["level0", "level1"])
@pytest.mark.parametrize("causal", [False, True], ids=["bidir", "causal"])
def test_attention_tile_fwd_and_vjp_compile(one_chip, heads, causal):
    fn = dispatch.get_impl("attention_tile", "pallas")
    rows = ((64, 128, heads * 64), jnp.bfloat16)

    def loss(q, k, v, positions):
        out = fn(q, k, v, positions, head_dim=64, causal=causal, scale=0.125,
                 theta=10000.0)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                         rows, rows, rows, ((64, 128), jnp.int32))
    assert "attention_tile_fwd" in text and "attention_tile_bwd" in text
    _assert_kernel(text, n=2)


# a cold serving prefill of GPT-Base: one prompt of 16 tokens, 12 heads of 64;
# the float32 case is the compute type of chip_smoke's 1x4 decode check
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_attention_tile_prefill_compiles(one_chip, dtype):
    fn = dispatch.get_impl("attention_tile", "pallas")
    rows = ((1, 16, H * D), dtype)
    text = _compile_text(
        lambda q, k, v, pos: fn(q, k, v, pos, head_dim=D, causal=True,
                                scale=D ** -0.5, theta=10000.0),
        one_chip, rows, rows, rows, ((1, 16), jnp.int32))
    assert "attention_tile_fwd" in text
    _assert_kernel(text)


def test_attention_tile_train_step_layout(one_chip, monkeypatch):
    """In a whole train step the kernels read the projections' own output
    and nothing float32 of the attention's [B, S, H, D] shape is left: no
    transpose or copy sits between the projections and the kernels."""
    import re

    from repro.configs.paper_models import bert_proxy
    from repro.models.api import build_model

    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    cfg = bert_proxy(d_model=256, n_layers=2, vocab=512).replace(
        remat="full", compute_dtype=jnp.bfloat16)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def step(params, tokens, labels):
        return jax.grad(lambda p: model.loss(p, {"tokens": tokens,
                                                 "labels": labels})[0])(params)

    shard = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), t)
    toks = jax.ShapeDtypeStruct((8, 128), jnp.int32, sharding=one_chip)
    text = jax.jit(step).lower(shard(params), toks, toks).compile().as_text()
    calls = [l for l in text.splitlines() if "custom_call_target=\"tpu_custom_call\"" in l
             and re.search(r"attention_tile_(fwd|bwd)", l)]
    assert len(calls) >= 3  # forward, recomputed forward, backward
    assert "f32[8,128,4,64]" not in text
    for line in calls:
        # operands: the rotary table (cos, sin), then the [B, S, H*D] rows
        operands = re.search(r"custom-call\(([^)]*)\)", line).group(1)
        rows = re.sub(r"/\*index=\d+\*/", "", operands).split(", ")[2:]
        # (a copy-start/-done pair moves a buffer between memory spaces)
        assert not [r for r in rows if re.fullmatch(r"%(copy|transpose)(\.\d+)?", r)], line


def _gpt_base_step_text(one_chip, **replace):
    """The compiled level-0 V-cycle train step of GPT-Base at 2 x 1024."""
    from repro.config import MultiLevelConfig, TrainConfig
    from repro.configs import get_config
    from repro.core.vcycle import VCycleRunner
    from repro.optim import adamw_init

    cfg = get_config("gpt-base").replace(**replace)
    tc = TrainConfig(batch_size=2, seq_len=S)
    runner = VCycleRunner(cfg, MultiLevelConfig(n_levels=2, alpha=0.5), tc,
                          batch_fn=lambda g: None, seed=0)
    params = jax.eval_shape(runner.models[0].init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: adamw_init(p, tc=tc), params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, S), jnp.int32)}
    shard = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), t)
    step = runner.step_fn(0).__wrapped__
    return step.lower(shard(params), shard(opt), shard(batch)).compile().as_text()


def test_gpt_base_train_step_takes_the_flash_kernels(one_chip, monkeypatch):
    """On a TPU the shapes alone put GPT-Base's 1024-token attention through
    the three named Pallas kernels, with no XLA flash scan left in the
    attention scope; pinning ``xla`` keeps that scan."""
    import re

    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    scan = re.compile(r'op_name="[^"]*/attention/while')
    text = _gpt_base_step_text(one_chip)
    for kernel in ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv"):
        assert re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", text), kernel
    assert not scan.search(text)
    pinned = _gpt_base_step_text(one_chip, kernel_backend="xla")
    assert scan.search(pinned) and "flash_attention_fwd" not in pinned
