"""Per-kernel sweeps: shapes x dtypes x registry backends, assert_allclose vs
the ref.py oracles through the one dispatch entry point (interpret mode
executes the kernel body on CPU; TPU is the target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, flash_attention, ops, ref

# every backend that resolves to itself on this host ("pallas" downgrades to
# the interpreter off-TPU -- skip the duplicate sweep)
RESOLVABLE = tuple(b for b in dispatch.BACKENDS
                   if dispatch.resolve_backend("coalesce_pair", b) == b)


@pytest.mark.parametrize("shape", [
    (1, 2, 128, 128, 64), (2, 4, 128, 128, 32), (1, 2, 256, 256, 64),
    (2, 2, 128, 256, 64),  # cross-length (non-causal only)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(shape, dtype, causal):
    B, H, S, T, D = shape
    if causal and S != T:
        pytest.skip("causal requires S == T in this kernel")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, H, T, D), dtype)
    v = jax.random.normal(ks[2], (B, H, T, D), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.naive_attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("block", [64, 128])
def test_flash_attention_block_invariance(block):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
    a = ops.flash_attention(q, k, v, block_q=block, block_k=block)
    b = ref.naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_pads_unaligned_lengths(dtype):
    """A causal length no aligned block divides (200) is padded, not handed
    to XLA: values and grads still match the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(10), 4)
    q, k, v, ct = (jax.random.normal(kk, (1, 2, 200, 32), dtype) for kk in ks)
    got = ops.flash_attention_vjp(q, k, v, causal=True, block_q=64, block_k=64)
    want = ref.naive_attention(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * ct.astype(jnp.float32))

    g = jax.grad(loss(lambda q, k, v: ops.flash_attention_vjp(
        q, k, v, causal=True, block_q=64, block_k=64)), argnums=(0, 1, 2))(q, k, v)
    w = jax.grad(loss(lambda q, k, v: ref.naive_attention(q, k, v, causal=True)),
                 argnums=(0, 1, 2))(q, k, v)
    gtol = 6e-2 if dtype == jnp.bfloat16 else 1e-4
    for a, b in zip(g, w):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=gtol, rtol=gtol)


# (S, tile): a diagonal tile walked in 2 and in 4 sub-blocks; a full tile
# below the diagonal beside two walked ones; a padded length (1500 -> 2048)
# whose padded keys sit in a walked tile; an unaligned whole tile, computed
# in one piece
@pytest.mark.parametrize("S,block", [(512, 512), (1024, 1024), (2048, 1024),
                                     (1500, 1024), (1000, 1024)],
                         ids=["S512", "S1024", "S2048", "S1500_padded", "S1000_whole"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_diagonal_walk(S, block, dtype):
    """Causal tiles larger than ``DIAGONAL_BLOCK`` are walked in sub-blocks:
    values and q/k/v gradients still match the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, ct = (jax.random.normal(kk, (1, 1, S, 32), dtype) for kk in ks)

    def flash(q, k, v):
        return ops.flash_attention_vjp(q, k, v, causal=True, block_q=block,
                                       block_k=block)

    def oracle(q, k, v):
        return ref.naive_attention(q, k, v, causal=True)

    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(flash(q, k, v), np.float32),
                               np.asarray(oracle(q, k, v), np.float32),
                               atol=tol, rtol=tol)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * ct.astype(jnp.float32))

    g = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    w = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    gtol = 6e-2 if dtype == jnp.bfloat16 else 1e-4
    for a, b in zip(g, w):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=gtol, rtol=gtol)


@pytest.mark.parametrize("bq,c", [(1024, 256), (512, 256), (2048, 256),
                                  (256, 256), (128, 128), (64, 64), (1000, 1000),
                                  (640, 640)])
def test_diagonal_block(bq, c):
    assert flash_attention.diagonal_block(bq) == c


@pytest.mark.parametrize("S,bq,c,share", [
    (1024, 1024, 256, 0.625),   # one tile, 10 of its 16 sub-blocks
    (512, 512, 256, 0.75),      # 3 of 4
    (2048, 1024, 256, 0.5625),  # a full tile beside two walked ones
    (1000, 1000, 1000, 1.0),    # one whole tile
    (256, 256, 256, 1.0),
    (256, 64, 64, 0.625),       # tiles skipped by the grid alone
])
def test_causal_share(S, bq, c, share):
    assert flash_attention.causal_share(S, bq, c) == share


@pytest.mark.parametrize("causal,bq,bk", [
    (True, 1024, 1024), (True, 512, 512), (True, 256, 256), (True, 64, 64),
    (True, 1024, 512), (False, 1024, 1024), (False, 256, 256)])
def test_causal_steps_schedule(causal, bq, bk):
    """Which work each grid step of a 4 x 4 tile grid runs: non-causal calls
    and causal tiles of 256 rows or fewer (or not square) run the whole tile
    wherever it holds an admitted score; larger square causal tiles run
    whole below the diagonal and are walked on it; nothing runs above it."""
    for qi in range(4):
        for ki in range(4):
            ran = []
            flash_attention._causal_steps(
                qi, ki, causal=causal, bq=bq, bk=bk,
                tile=lambda: ran.append("tile"),
                diagonal=lambda c: ran.append(("walk", c)))
            if not causal:
                want = ["tile"]
            elif bq > 256 and bq == bk:
                want = ["tile"] if ki < qi else [("walk", 256)] if ki == qi else []
            else:
                want = ["tile"] if ki * bk <= (qi + 1) * bq - 1 else []
            assert ran == want, (qi, ki)


@pytest.mark.parametrize("backend", RESOLVABLE)
@pytest.mark.parametrize("shape", [(8, 8), (512, 384), (64, 640), (768, 64)])
@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("w0", [0.5, 1.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coalesce_pair_sweep(backend, shape, axis, w0, dtype):
    w = jax.random.normal(jax.random.PRNGKey(2), shape, dtype)
    got = dispatch.dispatch("coalesce_pair", w, axis=axis, w0=w0, block=128,
                            backend=backend)
    want = ref.coalesce_pair_ref(w, axis=axis, w0=w0)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_coalesce_pair_matches_paper_operator():
    """Kernel == the actual projections used by core (F_out 'stack' variant)."""
    from repro.core import projections as proj

    n = 128
    w = jax.random.normal(jax.random.PRNGKey(3), (n, 96), jnp.float32)
    m = proj.width_mats(n, "stack")
    want = jnp.asarray(m.F_in, jnp.float32) @ w  # in-axis: F_in (weights 1.0)
    got = ops.coalesce_pair(w, axis=0, w0=1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    want2 = w.T @ jnp.asarray(m.F_out, jnp.float32)  # out-axis on dim1
    got2 = ops.coalesce_pair(w.T, axis=1, w0=0.5)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2), atol=1e-5)


@pytest.mark.parametrize("backend", RESOLVABLE)
@pytest.mark.parametrize("shape", [(33,), (1000, 37), (16, 16, 16)])
@pytest.mark.parametrize("alpha", [0.0, 0.25, 1.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_interp_axpy_sweep(backend, shape, alpha, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    a = jax.random.normal(ks[0], shape, dtype)
    b = jax.random.normal(ks[1], shape, dtype)
    got = dispatch.dispatch("interp_axpy", a, b, alpha, backend=backend)
    want = ref.interp_axpy_ref(a, b, alpha)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("backend", RESOLVABLE)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_backends_sweep(backend, causal):
    """Every registered flash_attention backend vs the naive oracle."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.float32)
    got = dispatch.dispatch("flash_attention", q, k, v, causal=causal,
                            block_q=64, block_k=64, backend=backend)
    want = ref.naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("backend", RESOLVABLE)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_dense_reassembly(backend, dtype):
    """Block-table decode == dense attention over the contiguously reassembled
    cache, for full pages, a partial tail page, and out-of-order page ids."""
    B, KH, G, D, N, P, M = 2, 2, 3, 32, 10, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, KH, G, D), dtype)
    k_pages = jax.random.normal(ks[1], (N, KH, P, D), dtype)
    v_pages = jax.random.normal(ks[2], (N, KH, P, D), dtype)
    bt = jnp.array([[7, 2, 9], [4, 1, 0]], jnp.int32)  # row 1: padded tail
    lengths = jnp.array([3 * P, P + 5, ], jnp.int32)
    got = dispatch.dispatch("paged_attention_decode", q, k_pages, v_pages,
                            bt, lengths, backend=backend)
    # dense oracle: gather each row's pages contiguously, run naive attention
    # with the padding masked by truncating to length
    outs = []
    for b in range(B):
        L = int(lengths[b])
        k = k_pages[bt[b]].transpose(0, 2, 1, 3).reshape(M * P, KH, D)[:L]
        v = v_pages[bt[b]].transpose(0, 2, 1, 3).reshape(M * P, KH, D)[:L]
        # [1, KH, G, D] x [1, KH, L, D] via the naive oracle's B,H,S,T layout
        o = ref.naive_attention(q[b][None].reshape(1, KH * G, 1, D).astype(jnp.float32),
                                jnp.repeat(k.transpose(1, 0, 2), G, axis=0)[None].astype(jnp.float32),
                                jnp.repeat(v.transpose(1, 0, 2), G, axis=0)[None].astype(jnp.float32),
                                causal=False)
        outs.append(o.reshape(KH, G, D))
    want = jnp.stack(outs)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("backend", RESOLVABLE)
def test_paged_attention_table_padding_ignored(backend):
    """Padding entries (null page 0) past ceil(len/P) must not affect the
    output: growing the table with null pages is a no-op."""
    B, KH, G, D, N, P = 1, 2, 2, 16, 6, 4
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, KH, G, D), jnp.float32)
    k_pages = jax.random.normal(ks[1], (N, KH, P, D), jnp.float32)
    v_pages = jax.random.normal(ks[2], (N, KH, P, D), jnp.float32)
    lengths = jnp.array([2 * P - 1], jnp.int32)
    narrow = dispatch.dispatch("paged_attention_decode", q, k_pages, v_pages,
                               jnp.array([[3, 5]], jnp.int32), lengths, backend=backend)
    wide = dispatch.dispatch("paged_attention_decode", q, k_pages, v_pages,
                             jnp.array([[3, 5, 0, 0]], jnp.int32), lengths, backend=backend)
    np.testing.assert_allclose(np.asarray(narrow), np.asarray(wide), atol=1e-6)


def test_paged_attention_ops_wrapper():
    """The jit'd public wrapper resolves interpret mode off-TPU and agrees
    with the gather reference (which reads the page-major [N, P, KH, D]
    layout of the head-major pool)."""
    q, kp, vp = (jax.random.normal(k, s, jnp.float32) for k, s in zip(
        jax.random.split(jax.random.PRNGKey(9), 3),
        [(2, 2, 2, 16), (8, 2, 4, 16), (8, 2, 4, 16)]))
    bt = jnp.array([[1, 2], [3, 0]], jnp.int32)
    lengths = jnp.array([7, 4], jnp.int32)
    got = ops.paged_attention_decode(q, kp, vp, bt, lengths)
    want = ref.paged_attention_ref(q, jnp.swapaxes(kp, 1, 2),
                                   jnp.swapaxes(vp, 1, 2), bt, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_attention_vjp_bf16():
    """The differentiable kernel wrapper holds bf16 inputs to bf16 tolerance."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.bfloat16)
    got = ops.flash_attention_vjp(q, k, v, causal=True, block_q=64, block_k=64)
    want = ref.naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)
    grads = jax.grad(lambda q, k, v: jnp.sum(ops.flash_attention_vjp(
        q, k, v, causal=True, block_q=64, block_k=64).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    assert all(g.dtype == jnp.bfloat16 for g in grads)
