"""Hypothesis property tests on the system's invariants."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="dev-only dependency (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import projections as proj
from repro.core.vcycle import History, flops_to_reach
from repro.launch.mesh import make_mesh

even = st.integers(min_value=1, max_value=64).map(lambda k: 2 * k)


@settings(max_examples=30, deadline=None)
@given(n=even, variant=st.sampled_from(["stack", "adj"]))
def test_width_inverse_properties(n, variant):
    m = proj.width_mats(n, variant)
    np.testing.assert_allclose(m.T_out @ m.F_out, np.eye(n // 2), atol=1e-10)
    np.testing.assert_allclose(m.F_in @ m.T_in, np.eye(n // 2), atol=1e-10)
    # D∘C projection is an idempotent averaging map (symmetric-neuron structure)
    P = m.F_out @ m.T_out  # [n, n]
    np.testing.assert_allclose(P @ P, P, atol=1e-10)


@settings(max_examples=30, deadline=None)
@given(L=st.integers(min_value=1, max_value=100), variant=st.sampled_from(["adj", "stack"]))
def test_depth_inverse_properties(L, variant):
    d = proj.depth_mats(L, variant)
    np.testing.assert_allclose(d.G @ d.R, np.eye(d.R.shape[1]), atol=1e-10)
    np.testing.assert_allclose((d.R @ d.G).sum(0), np.ones(L), atol=1e-10)


@settings(max_examples=20, deadline=None)
@given(n=even, c=st.integers(min_value=1, max_value=32),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_general_F_normalization(n, c, seed):
    """Paper §3.1: F_out may be ANY full-column-rank matrix; the derived
    T/F_in normalizations must still invert on the small side."""
    rng = np.random.default_rng(seed)
    F = rng.normal(size=(n, n // 2))
    # ensure strictly positive diagonal energy so colsums are non-degenerate
    F += np.vstack([np.eye(n // 2), np.eye(n // 2)])
    m = proj.derive_width(F)
    # value-scale stability: colsum normalization makes T_out F_out row sums finite
    assert np.all(np.isfinite(m.T_out)) and np.all(np.isfinite(m.T_in))


@settings(max_examples=20, deadline=None)
@given(alpha=st.floats(min_value=0.0, max_value=1.0),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_interpolation_convexity(alpha, seed):
    from repro.core.operators import interpolate

    rng = np.random.default_rng(seed)
    a = {"w": jnp.asarray(rng.normal(size=(6, 6)), jnp.float32)}
    b = {"w": jnp.asarray(rng.normal(size=(6, 6)), jnp.float32)}
    out = np.asarray(interpolate(a, b, float(alpha))["w"])
    lo = np.minimum(np.asarray(a["w"]), np.asarray(b["w"]))
    hi = np.maximum(np.asarray(a["w"]), np.asarray(b["w"]))
    assert (out >= lo - 1e-5).all() and (out <= hi + 1e-5).all()


@settings(max_examples=20, deadline=None)
@given(losses=st.lists(st.floats(min_value=0.1, max_value=10.0), min_size=6, max_size=40))
def test_flops_to_reach_monotone(losses):
    h = History()
    for i, l in enumerate(losses):
        h.log(float(i + 1), l, i, 0)
    _, sm = h.smoothed(5)
    t1 = flops_to_reach(h, float(min(sm)) + 1e-9)
    t2 = flops_to_reach(h, float(min(sm)) + 1.0)
    if t1 is not None and t2 is not None:
        assert t2 <= t1  # easier targets are reached no later


# ---------------------------------------------------------------------------
# checkpoint round-trips: arbitrary leaf names, dtypes and layouts


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


# any character except the tree separator "/" (and surrogates, which cannot
# encode); exercises unicode, "%", spaces, dots -- the v2 percent-encoding
# and the v3 JSON-only names must both be injective over all of these
leaf_names = st.text(
    alphabet=st.characters(blacklist_characters="/",
                           blacklist_categories=("Cs",)),
    min_size=1, max_size=8)

_DTYPES = [np.float32, np.float16, np.int32, np.int8, np.uint16, np.bool_]


@st.composite
def leaf_arrays(draw):
    dtype = np.dtype(draw(st.sampled_from(_DTYPES + [_bf16()])))
    shape = tuple(draw(st.lists(st.integers(1, 4), min_size=0, max_size=3)))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    if dtype == np.bool_:
        return rng.integers(0, 2, size=shape).astype(bool)
    if dtype.kind in "fV" or str(dtype) == "bfloat16":
        return rng.normal(size=shape).astype(dtype)
    return rng.integers(-100, 100, size=shape).astype(dtype)


@settings(max_examples=20, deadline=None)
@given(leaves=st.dictionaries(leaf_names, leaf_arrays(), min_size=1, max_size=4),
       dedup=st.booleans(), step=st.integers(1, 10**6))
def test_checkpoint_roundtrip_bit_exact(leaves, dedup, step):
    """Arbitrary leaf names (unicode, "%", literal "__"), dtypes (incl.
    bfloat16) and shapes (incl. 0-d) survive save -> restore bit-exactly, in
    BOTH the v2 whole-file layout and the content-addressed v3 layout."""
    from repro.checkpoint import CheckpointManager

    # always include the historically-corrupting names alongside the drawn
    # ones: a literal "__" (the pre-v2 separator), a raw "%", and unicode
    leaves = dict(leaves)
    leaves["w__gate"] = np.arange(3, dtype=np.float32)
    leaves["100% ünïcode"] = np.float32(7.5).reshape(())
    tree = {"params": leaves, "nested": {"inner": dict(leaves)}}
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, dedup=dedup)
        cm.save(step, tree, meta={"step": step})
        like = jax.tree.map(lambda v: jnp.zeros(v.shape, v.dtype), tree)
        out, meta = cm.restore(like)
        assert meta["step"] == step
        flat_in, flat_out = jax.tree.leaves(tree), jax.tree.leaves(out)
        assert len(flat_in) == len(flat_out)
        for a, b in zip(flat_in, flat_out):
            got = np.asarray(jax.device_get(b))
            assert got.dtype == a.dtype, (got.dtype, a.dtype)
            np.testing.assert_array_equal(got, np.asarray(a))


@settings(max_examples=10, deadline=None)
@given(dedup=st.booleans(), rows=st.integers(1, 4),
       seed=st.integers(0, 2**31 - 1))
def test_checkpoint_roundtrip_across_shard_layouts(dedup, rows, seed):
    """Restoring onto an explicit mesh sharding (the elastic re-shard path)
    is still bit-exact for either layout -- checkpoints are logical."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint import CheckpointManager

    rng = np.random.default_rng(seed)
    w = rng.normal(size=(2 * rows, 4)).astype(np.float32)
    tree = {"params": {"w": w, "b": rng.normal(size=(4,)).astype(np.float16)}}
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = {"params": {"w": NamedSharding(mesh, P("data", None)),
                     "b": NamedSharding(mesh, P())}}
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, dedup=dedup)
        cm.save(1, tree, meta={"step": 1})
        like = jax.tree.map(lambda v: jnp.zeros(v.shape, v.dtype), tree)
        out, _ = cm.restore(like, shardings=sh)
        assert out["params"]["w"].sharding == sh["params"]["w"]
        np.testing.assert_array_equal(np.asarray(out["params"]["w"]), w)
        assert out["params"]["b"].dtype == np.float16


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       n=st.sampled_from([8, 16, 32]))
def test_cd_identity_random_tensors(seed, n):
    """C∘D == id on arbitrary tensors for any (axes, roles) combination."""
    from repro.core.operators import LevelMaps, _project_tree
    from repro.param import Spec

    rng = np.random.default_rng(seed)
    maps = LevelMaps(width={"embed": proj.width_mats(n, "stack"),
                            "mlp": proj.width_mats(2 * n, "adj")},
                     depth={"stage_0": proj.depth_mats(5, "adj")}).as_jnp()
    spec = Spec((5, n, 2 * n), ("layers", "embed", "mlp"), ("-", "in", "out"))
    small = jnp.asarray(rng.normal(size=(3, n // 2, n)), jnp.float32)
    specs = {"stage_0": {"w": spec}}
    de = _project_tree({"stage_0": {"w": small}}, specs, maps, "decoalesce", False)
    rt = _project_tree(de, specs, maps, "coalesce", False)
    np.testing.assert_allclose(np.asarray(rt["stage_0"]["w"]), np.asarray(small), atol=1e-5)


# ---------------------------------------------------------------------------
# serving page allocator (launch/paging.py)


def _allocator_invariants(alloc, live):
    """The pinned pool invariants: full free/held accounting, no page in two
    live tables except via refcounted sharing, refcount == holder count."""
    pool = alloc.pool
    free = set(pool._free)
    held = {}
    for table in live.values():
        assert len(set(table)) == len(table), "page assigned twice in one table"
        for pid in table:
            held[pid] = held.get(pid, 0) + 1
    for pid, n in held.items():
        assert pid != 0, "null page handed to a request"
        assert pid not in free, "page simultaneously free and held"
        assert pool.refcount(pid) == n, "refcount != number of live holders"
    assert set(pool._ref) == set(held), "allocated page held by no request (leak)"
    assert len(free) + len(pool._ref) == pool.capacity


@settings(max_examples=50, deadline=None)
@given(data=st.data())
def test_page_allocator_admit_complete_interleavings(data):
    """Arbitrary admit/complete/denied interleavings: never leak a page,
    never double-assign, shared prefix pages freed exactly when the last
    referencing request completes, pool empty after a full drain."""
    from repro.launch.paging import BlockAllocator

    P = data.draw(st.sampled_from([2, 4]), label="page_size")
    n_pages = data.draw(st.integers(min_value=4, max_value=24), label="n_pages")
    reuse = data.draw(st.booleans(), label="prefix_reuse")
    alloc = BlockAllocator(n_pages, P, prefix_reuse=reuse)
    live = {}
    rid = 0
    for _ in range(data.draw(st.integers(min_value=1, max_value=30), label="n_ops")):
        if data.draw(st.booleans(), label="admit?") or not live:
            # tiny alphabet + optional common stem -> frequent shared prefixes
            body = data.draw(st.lists(st.integers(0, 3), min_size=1, max_size=10),
                             label="prompt")
            if data.draw(st.booleans(), label="stem?"):
                body = [1, 2, 3, 4, 1, 2, 3, 4] + body
            total = len(body) + data.draw(st.integers(1, 8), label="max_new")
            got = alloc.admit(rid, body, total)
            if got is not None:
                table, reuse_len = got
                assert len(table) == alloc.pages_needed(total)
                assert reuse_len <= len(body) - 1  # >= 1 fresh tail token
                assert reuse_len % P == 0
                live[rid] = table
            else:
                # denied admit must not have touched any state
                _allocator_invariants(alloc, live)
            rid += 1
        else:
            victim = data.draw(st.sampled_from(sorted(live)), label="complete")
            alloc.complete(victim)
            del live[victim]
        _allocator_invariants(alloc, live)
    for r in sorted(live):
        alloc.complete(r)
        del live[r]
        _allocator_invariants(alloc, live)
    assert alloc.pool.n_used == 0
    assert alloc.prefix is None or len(alloc.prefix) == 0


@settings(max_examples=30, deadline=None)
@given(stem_pages=st.integers(min_value=1, max_value=3),
       tail_a=st.integers(min_value=1, max_value=5),
       tail_b=st.integers(min_value=1, max_value=5))
def test_shared_prefix_page_freed_on_last_release(stem_pages, tail_a, tail_b):
    """Two prompts sharing a stem share its full pages; those pages survive
    the first completion and free exactly at the second."""
    from repro.launch.paging import BlockAllocator, page_digests

    P = 4
    alloc = BlockAllocator(32, P)
    stem = list(range(stem_pages * P))
    ta, _ = alloc.admit(0, stem + [7] * tail_a, stem_pages * P + tail_a + 2)
    tb, reused = alloc.admit(1, stem + [9] * tail_b, stem_pages * P + tail_b + 2)
    assert reused == stem_pages * P
    shared = ta[:stem_pages]
    assert tb[:stem_pages] == shared
    assert all(alloc.pool.refcount(p) == 2 for p in shared)
    alloc.complete(0)
    assert all(alloc.pool.refcount(p) == 1 for p in shared)  # still referenced
    # digests still served from the survivor's pages
    assert len(alloc.prefix.lookup(page_digests(stem, P))) == stem_pages
    alloc.complete(1)
    assert all(alloc.pool.refcount(p) == 0 for p in shared)  # last ref freed
    assert alloc.pool.n_used == 0
    assert len(alloc.prefix) == 0


@settings(max_examples=50, deadline=None)
@given(data=st.data())
def test_speculative_length_protocol_invariants(data):
    """The speculative advance/mark_written/rollback protocol over arbitrary
    interleavings: committed length never exceeds the written high-water,
    written never exceeds the admission reserve (page-safety of speculative
    bursts), rollback always rewinds written to exactly the committed length
    and accounts every rewound position, and over-reserve writes raise
    instead of silently landing outside the block table."""
    from repro.launch.paging import BlockAllocator

    P = 4
    alloc = BlockAllocator(64, P, prefix_reuse=False)
    L = data.draw(st.integers(min_value=1, max_value=10), label="prompt_len")
    max_new = data.draw(st.integers(min_value=1, max_value=12), label="max_new")
    reserve = L + max_new
    assert alloc.admit(0, [1] * L, reserve) is not None
    rolled_expect = 0
    for _ in range(data.draw(st.integers(1, 25), label="n_ops")):
        op = data.draw(st.sampled_from(["advance", "mark", "rollback"]), label="op")
        if op == "advance":
            n = data.draw(st.integers(1, 4), label="n")
            if alloc.lengths[0] + n > reserve:
                with pytest.raises(ValueError, match="exceeds the admission reserve"):
                    alloc.advance(0, n)
            else:
                alloc.advance(0, n)
        elif op == "mark":
            k = data.draw(st.integers(1, 6), label="k")
            upto = alloc.lengths[0] + k
            if upto > reserve:
                with pytest.raises(ValueError, match="exceeds the admission reserve"):
                    alloc.mark_written(0, upto)
            else:
                alloc.mark_written(0, upto)
        else:
            rolled_expect += alloc.written[0] - alloc.lengths[0]
            alloc.rollback(0)
            assert alloc.written[0] == alloc.lengths[0]
        assert L <= alloc.lengths[0] <= alloc.written[0] <= reserve
    rolled_expect += alloc.written[0] - alloc.lengths[0]
    alloc.rollback(0)
    assert alloc.rolled_back_total == rolled_expect
    alloc.complete(0)
    assert 0 not in alloc.lengths and 0 not in alloc.written
    assert alloc.pool.n_used == 0


@settings(max_examples=50, deadline=None)
@given(data=st.data())
def test_reload_interleaving_allocator_invariants(data):
    """Live weight reloads interleaved with admits/ticks/completions at the
    allocator level: the pool invariants hold after every op, a weight swap's
    ``invalidate_prefix`` empties the cache WITHOUT touching pages still held
    by in-flight requests, no admit ever reuses a prefix page written under
    pre-swap weights (stale K/V), and the speculative draft pool -- sized one
    worst-case table per row -- never denies an admit the main pool granted."""
    from repro.launch.paging import BlockAllocator

    P = 4
    B = data.draw(st.integers(min_value=2, max_value=4), label="batch")
    MAX_TOTAL = 24
    max_pages = -(-MAX_TOTAL // P)
    n_pages = data.draw(st.integers(min_value=8, max_value=32), label="n_pages")
    alloc = BlockAllocator(n_pages, P, prefix_reuse=True)
    draft = BlockAllocator(B * max_pages + 1, P, prefix_reuse=False)
    live, dlive, reserve = {}, {}, {}
    page_epoch, epoch, rid = {}, 0, 0
    for _ in range(data.draw(st.integers(min_value=1, max_value=40),
                             label="n_ops")):
        op = data.draw(st.sampled_from(["admit", "tick", "complete", "reload"]),
                       label="op")
        if op == "admit" and len(live) < B:
            body = data.draw(st.lists(st.integers(0, 3), min_size=1,
                                      max_size=10), label="prompt")
            if data.draw(st.booleans(), label="stem?"):
                body = [1, 2, 3, 4, 1, 2, 3, 4] + body
            total = min(len(body) + data.draw(st.integers(1, 8),
                                              label="max_new"), MAX_TOTAL)
            if total <= len(body):
                total = len(body) + 1
            got = alloc.admit(rid, body, total)
            if got is not None:
                table, reuse_len = got
                n_reused = reuse_len // P
                for pid in table[:n_reused]:
                    # a prefix hit must come from pages admitted SINCE the
                    # last swap: stale K/V from old weights never serves
                    assert page_epoch[pid] == epoch, \
                        "stale prefix page reused across a weight swap"
                for pid in table[n_reused:]:
                    page_epoch[pid] = epoch
                live[rid] = table
                reserve[rid] = total
                dgot = draft.admit(rid, body, total)
                assert dgot is not None, \
                    "draft pool (one worst-case table per row) denied an admit"
                dlive[rid] = dgot[0]
            rid += 1
        elif op == "tick" and live:
            row = data.draw(st.sampled_from(sorted(live)), label="tick_row")
            if alloc.lengths[row] < reserve[row]:
                alloc.advance(row, 1)
                draft.advance(row, 1)
        elif op == "complete" and live:
            victim = data.draw(st.sampled_from(sorted(live)), label="complete")
            alloc.complete(victim)
            draft.complete(victim)
            for d in (live, dlive, reserve):
                del d[victim]
        elif op == "reload":
            # the engine swaps weights: prefix entries derived from the old
            # weights are dropped; holders keep their pages untouched
            n_held_before = alloc.pool.n_used
            alloc.invalidate_prefix()
            epoch += 1
            assert len(alloc.prefix) == 0
            assert alloc.pool.n_used == n_held_before  # in-flight unharmed
        _allocator_invariants(alloc, live)
        _allocator_invariants(draft, dlive)
    assert alloc.invalidations_total == epoch
    for r in sorted(live):
        alloc.complete(r)
        draft.complete(r)
        del live[r], dlive[r]
        _allocator_invariants(alloc, live)
        _allocator_invariants(draft, dlive)
    assert alloc.pool.n_used == 0 and draft.pool.n_used == 0
