"""Serving-loop tests, run against BOTH engines (slots oracle + paged KV):
the continuous-batching lifecycle (admit -> decode -> slot/pages free on
length budget -> re-prefill into the freed capacity), the oversized-prompt
guards, and the paged engine's extra contracts -- token-for-token greedy
equivalence with the slot oracle (prefix reuse on and off), page-pool
admission/exhaustion behavior, and zero leaked pages after a drain.

The decode-policy suite at the bottom pins the speculative contract: the
coalesced level-1 draft may be arbitrarily wrong (random weights, or a
sabotaged draft that disagrees on the first token of every round) and the
emitted stream must STILL be token-for-token identical to greedy decode,
with rejected positions rewound through the allocator's rollback protocol.

The mesh-sharded smoke at the bottom runs in a subprocess (2 forced host
devices): --mesh 1x2 paged decode must emit the unsharded engine's exact
stream, with the K/V page pools genuinely model-sharded, across a hot weight
swap."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from helpers import tiny_dense, tiny_mla
from repro.config import MultiLevelConfig
from repro.configs import get_config
from repro.core import operators as ops
from repro.launch.mesh import make_mesh
from repro.launch.serve import (EngineCore, PagedServer, Request, Server,
                                SpeculativePolicy, make_server)
from repro.models.api import build_model


@pytest.fixture(scope="module")
def server_cfg():
    return get_config("tinyllama-1.1b", smoke=True)


@pytest.fixture(params=["slots", "paged"])
def engine(request):
    return request.param


def _server(cfg, engine, batch, max_seq, **kw):
    return make_server(cfg, engine=engine, batch=batch, max_seq=max_seq,
                       page_size=8, **kw)


# ---------------------------------------------------------------------------
# lifecycle (both engines)


def test_continuous_batching_recycles_slots(server_cfg, engine):
    """More requests than slots: finished sequences must free their capacity
    and the next request must prefill into it (the core of continuous
    batching) -- identical contract for both engines."""
    srv = _server(server_cfg, engine, batch=2, max_seq=48)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 100, size=int(rng.integers(4, 9))),
                    max_new=3) for i in range(5)]
    done = srv.run(reqs)
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    assert all(len(r.out) == 3 for r in done)  # length budget frees the slot
    assert srv.rejected == []
    assert all(a is None for a in srv.active)  # every slot recycled and freed
    # slot recycling really happened: 5 requests through 2 slots
    assert len(done) > srv.batch


def test_admit_rejects_oversized_prompt(server_cfg, engine):
    """len(prompt) > max_seq - 1 used to crash _splice with a negative pad (or
    silently drop cache writes once pos ran past max_seq); admit must refuse
    -- in both engines, with the same error contract."""
    srv = _server(server_cfg, engine, batch=2, max_seq=16)
    with pytest.raises(ValueError, match="cannot be admitted"):
        srv.admit(Request(rid=0, prompt=np.arange(16, dtype=np.int64), max_new=4))
    with pytest.raises(ValueError, match="cannot be admitted"):
        srv.admit(Request(rid=1, prompt=np.arange(40, dtype=np.int64), max_new=4))
    # boundary: max_seq - 1 tokens still fit (one decode step, then freed)
    assert srv.admit(Request(rid=2, prompt=np.arange(15, dtype=np.int64), max_new=4))


def test_run_drops_oversized_instead_of_wedging(server_cfg, engine):
    """An oversized request at the queue head must be routed to ``rejected``;
    the well-formed requests behind it must still complete."""
    srv = _server(server_cfg, engine, batch=2, max_seq=16)
    reqs = [Request(rid=0, prompt=np.arange(20, dtype=np.int64), max_new=2),
            Request(rid=1, prompt=np.arange(4, dtype=np.int64), max_new=2),
            Request(rid=2, prompt=np.arange(5, dtype=np.int64), max_new=2)]
    done = srv.run(reqs)
    assert [r.rid for r in srv.rejected] == [0]
    assert sorted(r.rid for r in done) == [1, 2]
    assert all(len(r.out) == 2 for r in done)


def test_pos_capped_at_last_cache_index(server_cfg, engine):
    """A sequence admitted near the budget edge frees after one token and its
    pos never exceeds max_seq - 1 (decode cache writes past that are silently
    dropped by jax's out-of-range .at[].set semantics)."""
    srv = _server(server_cfg, engine, batch=1, max_seq=12)
    done = srv.run([Request(rid=0, prompt=np.arange(11, dtype=np.int64),
                            max_new=50)])
    assert len(done) == 1 and len(done[0].out) >= 1
    assert int(srv.pos[0]) <= srv.max_seq - 1


# ---------------------------------------------------------------------------
# paged-vs-slots greedy equivalence (the acceptance oracle)


def _request_mix(vocab: int, seed: int = 1):
    """Mixed lengths + a shared-prefix cohort + one oversized prompt."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, size=20)
    reqs = [Request(rid=i, prompt=rng.integers(0, vocab, size=int(rng.integers(4, 14))),
                    max_new=6) for i in range(5)]
    for i in range(5, 8):
        tail = rng.integers(0, vocab, size=3 + i)
        reqs.append(Request(rid=i, prompt=np.concatenate([shared, tail]), max_new=6))
    reqs.append(Request(rid=99, prompt=rng.integers(0, vocab, size=64), max_new=4))
    return reqs


@pytest.mark.parametrize("prefix_reuse", [True, False])
def test_paged_matches_slots_token_for_token(prefix_reuse):
    """Same request list through both engines -> identical greedy outputs per
    request AND identical rejections, with prefix reuse on and off.  f32
    compute so bf16 argmax ties can't flake the comparison."""
    cfg = tiny_dense(compute_dtype="float32")
    results = {}
    for engine in ("slots", "paged"):
        srv = make_server(cfg, engine=engine, batch=3, max_seq=48, page_size=8,
                          prefix_reuse=prefix_reuse)
        done = srv.run(_request_mix(cfg.vocab_size))
        results[engine] = ({r.rid: r.out for r in done},
                           sorted(r.rid for r in srv.rejected))
    assert results["paged"][1] == results["slots"][1] == [99]
    assert results["paged"][0] == results["slots"][0]


def test_paged_matches_slots_mla():
    """Equivalence also holds for the MLA (compressed-latent) cache layout."""
    cfg = tiny_mla(compute_dtype="float32")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (5, 9, 12)]
    results = {}
    for engine in ("slots", "paged"):
        srv = make_server(cfg, engine=engine, batch=2, max_seq=32, page_size=4)
        done = srv.run([Request(rid=i, prompt=p, max_new=4)
                        for i, p in enumerate(prompts)])
        results[engine] = {r.rid: r.out for r in done}
    assert results["paged"] == results["slots"]


def test_prefix_reuse_saves_prefill_and_stays_exact():
    """The shared-prefix cohort must actually skip prefill work (saved > 0)
    while still emitting the slot oracle's exact tokens (covered above); here
    we pin the accounting: saved tokens only with reuse on, and the computed
    count shrinks by exactly the saved amount."""
    cfg = tiny_dense(compute_dtype="float32")
    reqs = _request_mix(cfg.vocab_size)
    total_prompt = sum(len(r.prompt) for r in reqs if len(r.prompt) <= 47)
    on = make_server(cfg, engine="paged", batch=3, max_seq=48, page_size=8)
    on.run(_request_mix(cfg.vocab_size))
    off = make_server(cfg, engine="paged", batch=3, max_seq=48, page_size=8,
                      prefix_reuse=False)
    off.run(_request_mix(cfg.vocab_size))
    assert on.prefill_tokens_saved > 0
    assert off.prefill_tokens_saved == 0
    assert off.prefill_tokens_computed == total_prompt
    assert on.prefill_tokens_computed == total_prompt - on.prefill_tokens_saved


# ---------------------------------------------------------------------------
# page-pool admission behavior


def test_pool_exhaustion_queues_until_pages_free():
    """A pool too small for all requests at once must make later requests
    wait for completions (not crash, not reject), and still finish them all."""
    cfg = tiny_dense(compute_dtype="float32")
    rng = np.random.default_rng(7)
    # each request needs ceil(min(10+4, 32)/4) = 4 pages; pool holds 8 ->
    # at most 2 in flight though batch would allow 4
    srv = make_server(cfg, engine="paged", batch=4, max_seq=32, page_size=4,
                      n_pages=9, prefix_reuse=False)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=10),
                    max_new=4) for i in range(5)]
    done = srv.run(reqs)
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    assert srv.rejected == []
    assert srv.pages_in_use_peak <= 8
    assert srv.alloc.pool.n_used == 0  # every page returned


def test_never_admittable_block_table_rejected():
    """A prompt whose worst-case block table exceeds the whole pool can never
    admit and must be rejected up front (not wedge the queue)."""
    cfg = tiny_dense(compute_dtype="float32")
    srv = make_server(cfg, engine="paged", batch=2, max_seq=64, page_size=4,
                      n_pages=5)  # capacity 4 pages = 16 positions
    reqs = [Request(rid=0, prompt=np.arange(30, dtype=np.int64), max_new=8),
            Request(rid=1, prompt=np.arange(6, dtype=np.int64), max_new=4)]
    done = srv.run(reqs)
    assert [r.rid for r in srv.rejected] == [0]
    assert [r.rid for r in done] == [1]


def test_pool_fully_free_after_drain():
    cfg = tiny_dense(compute_dtype="float32")
    srv = make_server(cfg, engine="paged", batch=3, max_seq=48, page_size=8)
    srv.run(_request_mix(cfg.vocab_size))
    assert srv.alloc.pool.n_used == 0
    assert srv.pages_in_use_peak > 0
    assert len(srv.alloc.live) == 0
    # prefix cache must not outlive its pages
    assert srv.alloc.prefix is None or len(srv.alloc.prefix) == 0


def test_reset_reuses_compiled_steps():
    """reset() must clear request/pool state but keep the compiled steps
    usable (the bench warmup contract)."""
    cfg = tiny_dense(compute_dtype="float32")
    srv = make_server(cfg, engine="paged", batch=2, max_seq=32, page_size=8)
    first = srv.run([Request(rid=0, prompt=np.arange(6, dtype=np.int64), max_new=3)])
    out0 = list(first[0].out)
    srv.reset()
    assert srv.done == [] and srv.alloc.pool.n_used == 0
    again = srv.run([Request(rid=1, prompt=np.arange(6, dtype=np.int64), max_new=3)])
    assert again[0].out == out0  # same prompt, same params -> same tokens


# ---------------------------------------------------------------------------
# decode policies: scheduler/policy split + speculative losslessness


def test_engines_share_scheduler_core():
    """The refactor's structural contract: admission, the run loop, token
    commit and reset live on ``EngineCore`` ONCE -- neither engine overrides
    them (engines only customize placement/retirement/decode hooks)."""
    for meth in ("fits", "admit", "run", "reset", "commit", "step", "set_params"):
        assert getattr(Server, meth) is getattr(EngineCore, meth)
        assert getattr(PagedServer, meth) is getattr(EngineCore, meth)


def test_make_server_rejects_unknown_engine_and_policy():
    cfg = tiny_dense(compute_dtype="float32")
    with pytest.raises(ValueError, match="unknown engine"):
        make_server(cfg, engine="vllm")
    with pytest.raises(ValueError, match="unknown policy"):
        make_server(cfg, engine="paged", policy="beam")
    with pytest.raises(TypeError, match="policy must be"):
        make_server(cfg, engine="paged", policy=42)
    with pytest.raises(NotImplementedError, match="paged engine"):
        make_server(cfg, engine="slots", policy="speculative")


def _greedy_oracle(cfg, reqs, **kw):
    srv = make_server(cfg, engine="paged", policy="greedy", **kw)
    done = srv.run(reqs)
    return {r.rid: r.out for r in done}


@pytest.mark.parametrize("prefix_reuse", [True, False])
def test_speculative_matches_greedy_token_for_token(prefix_reuse):
    """Random-init weights: the coalesced draft is essentially an unrelated
    model (accept rate ~0), the hardest losslessness stress -- every emitted
    token must still be the full model's argmax, so the stream is identical
    to greedy decode and to the slots oracle.  Rollback fires constantly and
    the pool must still drain clean."""
    cfg = tiny_dense(compute_dtype="float32")
    kw = dict(batch=3, max_seq=48, page_size=8, prefix_reuse=prefix_reuse)
    greedy = _greedy_oracle(cfg, _request_mix(cfg.vocab_size), **kw)
    srv = make_server(cfg, engine="paged", policy="speculative", draft_k=3, **kw)
    done = srv.run(_request_mix(cfg.vocab_size))
    assert {r.rid: r.out for r in done} == greedy
    st = srv.stats()
    assert st["drafted_tokens"] > 0
    assert st["rolled_back_positions"] > 0  # rejections actually rolled back
    assert srv.alloc.pool.n_used == 0  # drained clean despite rollbacks
    if prefix_reuse:
        assert srv.prefill_tokens_saved > 0  # reuse intact under speculation


def test_speculative_matches_greedy_mla():
    """Losslessness holds for the MLA (compressed-latent) paged layout too."""
    cfg = tiny_mla(compute_dtype="float32")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (5, 9, 12)]
    reqs = lambda: [Request(rid=i, prompt=p, max_new=5)
                    for i, p in enumerate(prompts)]
    kw = dict(batch=2, max_seq=32, page_size=4)
    greedy = _greedy_oracle(cfg, reqs(), **kw)
    srv = make_server(cfg, engine="paged", policy="speculative", draft_k=3, **kw)
    assert {r.rid: r.out for r in srv.run(reqs())} == greedy


def _width_consistent_params(cfg, ml):
    """decoalesce(width-only)(level-1 init): serving weights whose coalesced
    draft is function-identical to the full model (tests/test_operators.py
    pins the exact preservation)."""
    model = build_model(cfg)
    small_cfg = ops.coalesce_config(cfg, ml, width=True, depth=False)
    p_small = build_model(small_cfg).init(jax.random.PRNGKey(3))
    return ops.make_decoalesce_fn(model.specs(), cfg, ml,
                                  width=True, depth=False)(p_small)


def test_speculative_full_accept_on_consistent_params():
    """Projection-consistent weights via ``set_params`` (the hot-reload +
    draft-refresh path): the width-only draft agrees with the full model, so
    near-all drafted tokens are accepted, nothing rolls back, and the stream
    still matches greedy on the same weights."""
    cfg = tiny_dense(compute_dtype="float32", qk_norm=False, tie_embeddings=False)
    ml = MultiLevelConfig()
    p = _width_consistent_params(cfg, ml)
    rng = np.random.default_rng(11)
    reqs = lambda: [Request(rid=i, prompt=rng2, max_new=8)
                    for i, rng2 in enumerate(
                        rng.integers(0, cfg.vocab_size, size=(4, 7)))]
    fixed = reqs()
    kw = dict(batch=2, max_seq=48, page_size=8)
    gsrv = make_server(cfg, engine="paged", **kw)
    gsrv.set_params(p)
    greedy = {r.rid: r.out for r in gsrv.run([Request(r.rid, r.prompt, r.max_new)
                                              for r in fixed])}
    pol = SpeculativePolicy(k=4, ml=ml, draft_width=True, draft_depth=False)
    srv = make_server(cfg, engine="paged", policy=pol, **kw)
    srv.set_params(p)  # must re-project the draft (on_params), or accept ~0
    done = srv.run([Request(r.rid, r.prompt, r.max_new) for r in fixed])
    assert {r.rid: r.out for r in done} == greedy
    st = srv.stats()
    assert st["accept_rate"] > 0.9
    assert st["accepted_tokens"] > 0


def test_speculative_forced_rejection_rolls_back():
    """Sabotage the draft so it disagrees with the full model on the FIRST
    drafted token of every round (consistent weights make the honest draft
    argmax equal the full model's; +1 mod vocab then guarantees mismatch).
    Every round must reject at token 1, rewind its drafted positions through
    ``BlockAllocator.rollback``, and still emit the exact greedy stream."""
    cfg = tiny_dense(compute_dtype="float32", qk_norm=False, tie_embeddings=False)
    ml = MultiLevelConfig()
    p = _width_consistent_params(cfg, ml)
    rng = np.random.default_rng(13)
    prompts = rng.integers(0, cfg.vocab_size, size=(3, 6))
    reqs = lambda: [Request(rid=i, prompt=pr, max_new=6)
                    for i, pr in enumerate(prompts)]
    kw = dict(batch=2, max_seq=32, page_size=8)
    gsrv = make_server(cfg, engine="paged", **kw)
    gsrv.set_params(p)
    greedy = {r.rid: r.out for r in gsrv.run(reqs())}
    pol = SpeculativePolicy(k=3, ml=ml, draft_width=True, draft_depth=False)
    honest = pol._draft_argmax
    pol._draft_argmax = lambda logits: (honest(logits) + 1) % cfg.vocab_size
    srv = make_server(cfg, engine="paged", policy=pol, **kw)
    srv.set_params(p)
    done = srv.run(reqs())
    assert {r.rid: r.out for r in done} == greedy  # lossless under 100% rejection
    st = srv.stats()
    assert st["drafted_tokens"] > 0
    assert st["accept_rate"] <= 0.05  # near-ties may flake a single argmax
    assert srv.alloc.rolled_back_total > 0
    assert srv.alloc.pool.n_used == 0


def test_speculative_reset_and_reuse():
    """reset() must rebuild the draft pool/allocator alongside the main one
    and keep the compiled draft/verify steps usable (bench warmup contract)."""
    cfg = tiny_dense(compute_dtype="float32")
    srv = make_server(cfg, engine="paged", policy="speculative", draft_k=2,
                      batch=2, max_seq=32, page_size=8)
    first = srv.run([Request(rid=0, prompt=np.arange(6, dtype=np.int64), max_new=3)])
    out0 = list(first[0].out)
    srv.reset()
    assert srv.stats()["spec_rounds"] == 0  # policy stats cleared too
    again = srv.run([Request(rid=1, prompt=np.arange(6, dtype=np.int64), max_new=3)])
    assert again[0].out == out0


# ---------------------------------------------------------------------------
# mesh-sharded paged decode


def test_make_server_rejects_mesh_on_slots_engine():
    cfg = tiny_dense(compute_dtype="float32")
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="paged engine"):
        make_server(cfg, engine="slots", mesh=mesh)


@pytest.mark.slow
def test_mesh_sharded_paged_decode_matches_unsharded():
    """--mesh 1x2 smoke: the model-sharded paged decode step emits the
    unsharded engine's EXACT greedy stream (f32), the K/V page pools really
    are sharded over the "model" axis (not silently replicated), and a hot
    weight swap on the mesh server stays stream-identical.  Runs in a
    subprocess with 2 forced host devices (this process must keep its single
    real CPU device)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        from repro.launch.mesh import make_mesh
        import numpy as np
        from helpers import tiny_dense
        from repro.launch.serve import Request, make_server
        from repro.models.api import build_model

        cfg = tiny_dense(compute_dtype="float32")
        rng = np.random.default_rng(1)
        shared = rng.integers(0, cfg.vocab_size, size=16)
        prompts = [rng.integers(0, cfg.vocab_size, size=int(n))
                   for n in rng.integers(4, 14, size=4)]
        prompts += [np.concatenate([shared,
                                    rng.integers(0, cfg.vocab_size, size=3 + i)])
                    for i in range(2)]
        reqs = lambda base: [Request(rid=base + i, prompt=p, max_new=6)
                             for i, p in enumerate(prompts)]

        kw = dict(engine="paged", batch=3, max_seq=48, page_size=8)
        ref = make_server(cfg, **kw)
        mesh = make_mesh((1, 2), ("data", "model"))
        srv = make_server(cfg, mesh=mesh, **kw)

        # the page pools are genuinely model-sharded, not replicated
        specs = {str(leaf.sharding.spec) for leaf in jax.tree.leaves(srv.pages)}
        assert any("model" in s for s in specs), specs

        a = {r.rid: r.out for r in ref.run(reqs(0))}
        b = {r.rid: r.out for r in srv.run(reqs(0))}
        assert a == b, "sharded decode diverged from unsharded"

        # hot weight swap on the mesh server: still stream-identical
        p_new = build_model(cfg).init(jax.random.PRNGKey(42))
        ref.set_params(p_new)
        srv.set_params(p_new)
        a2 = {r.rid: r.out for r in ref.run(reqs(100))}
        b2 = {r.rid: r.out for r in srv.run(reqs(100))}
        assert {k: v for k, v in a2.items() if k >= 100} \\
            == {k: v for k, v in b2.items() if k >= 100}
        assert srv.params is not p_new  # re-placed onto the mesh sharding
        print("SHARDED_SERVE_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src" + os.pathsep + "tests")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_SERVE_OK" in out.stdout
