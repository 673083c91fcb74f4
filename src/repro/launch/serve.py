"""Batched serving driver: prefill + decode with continuous batching (lite).

The driver is split into two orthogonal layers:

  * an **engine** owns the KV cache layout and the admission/placement of a
    request into it.  Two engines share one scheduler core (``EngineCore``:
    admit / run / reset / commit defined once):

      - ``slots`` -- the original fixed-width decode batch over dense
        ``[batch, max_seq]`` caches; per-admit splice into a free slot.  Kept
        as the equivalence oracle (greedy decode must match token-for-token).
      - ``paged`` -- vLLM-style paged KV: cache leaves are a shared
        ``[n_pages, page_size, ...]`` pool, each request holds a block table
        of page ids (``launch/paging.py``), admission is by free-page count,
        and decode reads K/V through the block table (the
        ``paged_attention_decode`` op in ``kernels/dispatch.py``) so per-step
        cost scales with the pages a request actually occupies, not
        ``max_seq``.  Prompt pages are keyed by a rolling blake2b digest, so
        requests sharing a prompt prefix reuse its (refcounted) pages and
        only prefill the non-shared tail.

  * a **DecodePolicy** decides how scheduler ticks become committed tokens:

      - ``GreedyPolicy`` -- one full-model argmax per tick (prior behavior,
        both engines).
      - ``SpeculativePolicy`` -- self-speculative decoding from the paper's
        Coalescing operator: the level-1 coalesced model (a deterministic
        *projection* of the serving params, ``core/operators.py``) drafts k
        tokens per tick, one batched full-model verify step scores all of
        them against the paged cache, and the agreeing prefix plus one
        full-model token is committed.  Lossless for greedy sampling: every
        emitted token is a full-model argmax, so output is token-for-token
        identical to GreedyPolicy regardless of draft quality -- a bad draft
        only costs accept rate, never correctness.

Two orthogonal production seams sit on top:

  * **live weight reload** -- a ``ManifestWatcher`` polls the checkpoint
    store's ``manifest.json`` (shared-dir or no-shared-FS KV mode), diffs the
    new step's per-leaf chunk digests against what it already landed, and
    ships ONLY the changed leaves; the engine stages the result
    (``request_reload``) and swaps via ``set_params`` at a tick boundary
    once every in-flight request has drained -- zero dropped requests, the
    speculative draft re-projects, and the prefix cache is invalidated.
  * **mesh-sharded paged decode** -- ``PagedServer(mesh=...)`` jits the SAME
    ``make_paged_decode_step`` the ``decode_*`` dry-run cells compile with
    explicit shardings: params laid out by the serve rules, K/V page pools
    model-sharded over the kv-head axis (GQA; MLA's latent pools carry no
    head axis and replicate), block tables/tokens/positions replicated.

See ``src/repro/launch/README.md`` for the architecture notes.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import (CheckpointManager, _flatten, _put,
                                      _unflatten_into)
from repro.config import MultiLevelConfig
from repro.configs import get_config
from repro.core import operators as ops
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.paging import NULL_PAGE, BlockAllocator
from repro.models import lm as lm_lib
from repro.models.api import (build_model, make_paged_decode_step,
                              make_prefill_step, make_serve_step,
                              make_verify_step, serve_shardings)
from repro.param import Spec, is_spec


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S] int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)


def zeros_cache(cfg, batch: int, max_seq: int):
    cs = lm_lib.cache_specs(cfg, batch, max_seq)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype or cfg.compute_dtype),
                        cs, is_leaf=is_spec)


def zeros_paged_cache(cfg, n_pages: int, page_size: int):
    cs = lm_lib.paged_cache_specs(cfg, n_pages, page_size)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype or cfg.compute_dtype),
                        cs, is_leaf=is_spec)


def _bucket(n: int, cap: Optional[int] = None) -> int:
    """Next power of two >= n (bounds the jit retrace count for shapes that
    vary with load: decode table width, extend/verify tail length)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap) if cap is not None else b


def make_write_prompt(pool_specs):
    """Scatter a prefill cache ([layers, 1, L, ...] leaves) into a page pool
    at ``page_ids`` ([n_pg] int32, logical page order).  ``pool_specs`` is
    the pool's Spec tree: each leaf's "page_seq" axis says where its page
    dim sits (head-major GQA K/V, page-major MLA latents).  Shared by the
    paged engine's cold-prompt path and the speculative draft cache."""
    page_axes = jax.tree.map(lambda s: s.axes.index("page_seq"), pool_specs,
                             is_leaf=is_spec)

    def write_prompt(pages, prefill_cache, page_ids):
        n_pg = page_ids.shape[0]

        def one(pool, c, ax):
            P = pool.shape[ax]
            c = c[:, 0]  # [layers, L, ...]
            pad = [(0, 0)] * c.ndim
            pad[1] = (0, n_pg * P - c.shape[1])
            c = jnp.pad(c, pad)
            c = c.reshape(c.shape[0], n_pg, P, *c.shape[2:])
            c = jnp.moveaxis(c, 2, ax)
            return pool.at[:, page_ids].set(c.astype(pool.dtype))

        return jax.tree.map(one, pages, prefill_cache, page_axes)

    return write_prompt


# ---------------------------------------------------------------------------
# decode policies


class DecodePolicy:
    """Strategy turning scheduler ticks into committed tokens.

    The scheduler (``EngineCore``) owns request lifecycle -- admission, the
    queue, retirement -- and calls ``tick`` once per scheduling round; the
    policy decides what to decode and hands accepted tokens back through
    ``eng.commit(row, tokens)``.  Hooks observe lifecycle events so a policy
    can keep per-row state (the speculative draft cache) in sync.
    """

    name = "base"

    def bind(self, eng: "EngineCore") -> None:
        """One-time attach to a constructed engine (build compiled steps,
        allocate policy-owned state).  Raise for unsupported engines."""

    def tick(self, eng: "EngineCore") -> None:
        raise NotImplementedError

    def on_admit(self, eng: "EngineCore", row: int, req: Request) -> None:
        pass

    def on_complete(self, eng: "EngineCore", row: int, req: Request) -> None:
        pass

    def on_reset(self, eng: "EngineCore") -> None:
        pass

    def on_params(self, eng: "EngineCore") -> None:
        """Serving params changed (hot reload); refresh derived state."""

    def stats(self) -> Dict[str, Any]:
        return {"policy": self.name}


class GreedyPolicy(DecodePolicy):
    """One full-model argmax token per tick (both engines)."""

    name = "greedy"

    def tick(self, eng: "EngineCore") -> None:
        act = [i for i, r in enumerate(eng.active) if r is not None]
        nxt = eng.decode_once()
        for i in act:
            eng.commit(i, [nxt[i]])


class SpeculativePolicy(DecodePolicy):
    """Self-speculative decoding from the coalesced level-1 draft model.

    Per tick and per active row: draft up to ``k`` tokens with the level-1
    model (its params are ``coalesce(serving params)`` -- always in sync,
    refreshed by ``on_params``), then score the run ``[last_tok, d_1..d_k]``
    in ONE batched full-model verify step at positions ``pos..pos+k``, and
    commit the longest agreeing prefix plus the first disagreeing (or bonus)
    full-model argmax -- always >= 1 token per tick, so progress matches
    greedy in the worst case and is up to k+1 tokens per full-model step in
    the best.

    Losslessness: every committed token is ``argmax(verify logits)``; the
    draft only chooses *which* positions the verify step gets to score, so
    output is token-for-token identical to greedy decode by construction.

    Rollback: the verify step eagerly writes K/V for all k+1 positions.
    Rejected positions are rewound in the host-side length bookkeeping only
    (``BlockAllocator.mark_written`` / ``rollback``) -- the stale K/V needs
    no physical erase because attention reads are position-masked and the
    next committed token overwrites the slot.  The draft cache is rewound
    the same way via ``draft_pos``.

    Paged engine only: the draft runs over its own page pool with the same
    block-table discipline; the slots oracle stays greedy.
    """

    name = "speculative"

    def __init__(self, k: int = 4, ml: Optional[MultiLevelConfig] = None,
                 draft_width: bool = True, draft_depth: bool = True):
        if k < 1:
            raise ValueError(f"speculative draft length k must be >= 1, got {k}")
        self.k = k
        self.ml = ml or MultiLevelConfig()
        self.draft_width = draft_width
        self.draft_depth = draft_depth
        self._zero_stats()

    def _zero_stats(self) -> None:
        self.rounds = 0
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.draft_time_s = 0.0
        self.verify_time_s = 0.0

    def bind(self, eng: "EngineCore") -> None:
        if not isinstance(eng, PagedServer):
            raise NotImplementedError(
                "speculative decoding requires the paged engine "
                "(engine='paged'); the slots oracle stays greedy-only")
        self.draft_cfg, self._project = ops.make_draft_projection(
            eng.model.specs(), eng.cfg, self.ml,
            width=self.draft_width, depth=self.draft_depth)
        self.draft_model = build_model(self.draft_cfg)
        self.draft_params = self._project(eng.params)
        self.draft_prefill = jax.jit(make_prefill_step(self.draft_model))
        self.draft_step = jax.jit(make_paged_decode_step(self.draft_model),
                                  donate_argnums=(1,))
        self.verify = jax.jit(make_verify_step(eng.model), donate_argnums=(1,))
        self._write_draft = jax.jit(
            make_write_prompt(self.draft_model.paged_cache_specs(
                1, eng.page_size)), donate_argnums=(0,))
        # the draft cache gets its own pool, sized one worst-case table per
        # batch row (+ null page) so draft admission can never fail while a
        # row is free -- no un-admit path to maintain
        self._n_draft_pages = eng.batch * eng.max_pages_per_req + 1
        self._fresh(eng)

    def _fresh(self, eng: "PagedServer") -> None:
        self.draft_pages = zeros_paged_cache(self.draft_cfg,
                                             self._n_draft_pages, eng.page_size)
        self.draft_alloc = BlockAllocator(self._n_draft_pages, eng.page_size,
                                          prefix_reuse=False)
        self.draft_tables: List[Optional[List[int]]] = [None] * eng.batch
        self.draft_pos = np.zeros((eng.batch,), np.int32)
        # committed token at every position 0..pos, per row: the draft's
        # catch-up feed after a rejection re-reads history the main engine
        # no longer materializes anywhere else
        self.hist: List[Optional[List[int]]] = [None] * eng.batch

    # -- lifecycle hooks ----------------------------------------------------
    def on_admit(self, eng: "PagedServer", row: int, req: Request) -> None:
        L = len(req.prompt)
        total = min(L + req.max_new, eng.max_seq)
        got = self.draft_alloc.admit(req.rid, req.prompt, total)
        assert got is not None, "draft pool is sized for one table per row"
        table, _ = got
        toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
        _, pc = self.draft_prefill(self.draft_params, toks, None, None)
        n_pg = -(-L // eng.page_size)
        self.draft_pages = self._write_draft(
            self.draft_pages, pc, jnp.asarray(table[:n_pg], jnp.int32))
        self.draft_tables[row] = table
        self.draft_pos[row] = L
        self.hist[row] = [int(t) for t in req.prompt] + [int(eng.last_tok[row])]

    def on_complete(self, eng: "PagedServer", row: int, req: Request) -> None:
        self.draft_alloc.complete(req.rid)
        self.draft_tables[row] = None
        self.draft_pos[row] = 0
        self.hist[row] = None

    def on_reset(self, eng: "PagedServer") -> None:
        self._fresh(eng)
        self._zero_stats()

    def on_params(self, eng: "PagedServer") -> None:
        # re-project: the draft is a pure function of the serving params
        self.draft_params = self._project(eng.params)

    # -- the speculative tick ----------------------------------------------
    def _draft_argmax(self, logits) -> np.ndarray:
        """Draft proposals from draft-step logits ([B, V] -> [B] int32).
        A seam for tests: monkeypatching this to emit wrong tokens forces
        rejection without touching the verify path."""
        return np.asarray(jnp.argmax(logits, -1), np.int32)

    def _feed_token(self, eng: "PagedServer", i: int, p: int,
                    proposals: List[int]) -> int:
        """Token occupying position ``p`` for row ``i``: committed history up
        to ``pos`` (catch-up after acceptance/rejection), the row's own
        earlier proposal beyond it."""
        pos = int(eng.pos[i])
        if p <= pos:
            return self.hist[i][p]
        return proposals[p - pos - 1]

    def tick(self, eng: "PagedServer") -> None:
        act = [i for i, r in enumerate(eng.active) if r is not None]
        if not act:
            return
        self.rounds += 1
        # per-row speculation window: never draft past the request's token
        # budget or the last valid cache index, so the verify write stays
        # within the admission reserve (mark_written would raise otherwise)
        k_i = {i: max(0, min(self.k,
                             eng.active[i].max_new - len(eng.active[i].out) - 1,
                             eng.max_seq - 1 - int(eng.pos[i])))
               for i in act}
        drafts: Dict[int, List[int]] = {i: [] for i in act}
        # --- draft phase: batched S=1 level-1 steps.  Row i feeds positions
        # draft_pos[i] .. pos[i]+k_i[i]-1: committed catch-up tokens first
        # (they overwrite any rejected leftovers in the draft cache before a
        # later query could attend them), then its own fresh proposals.
        t0 = time.time()
        starts = {i: int(self.draft_pos[i]) for i in act}
        ends = {i: int(eng.pos[i]) + k_i[i] for i in act}
        M_b = _bucket(max(len(self.draft_tables[i]) for i in act),
                      cap=eng.max_pages_per_req)
        for j in range(max(ends[i] - starts[i] for i in act)):
            rows = [i for i in act if starts[i] + j < ends[i]]
            if not rows:
                break
            toks = np.zeros((eng.batch, 1), np.int32)
            poss = np.full((eng.batch, 1), -1, np.int32)  # idle row: null page
            bt = np.full((eng.batch, M_b), NULL_PAGE, np.int32)
            for i in rows:
                p = starts[i] + j
                toks[i, 0] = self._feed_token(eng, i, p, drafts[i])
                poss[i, 0] = p
                bt[i, :len(self.draft_tables[i])] = self.draft_tables[i]
            logits, self.draft_pages = self.draft_step(
                self.draft_params, self.draft_pages, jnp.asarray(toks),
                jnp.asarray(poss), jnp.asarray(bt))
            nxt = self._draft_argmax(logits)
            for i in rows:
                if starts[i] + j >= int(eng.pos[i]):  # predicts position > pos
                    drafts[i].append(int(nxt[i]))
        for i in act:
            self.draft_pos[i] = ends[i]
        self.draft_time_s += time.time() - t0
        self.drafted_tokens += sum(k_i.values())
        # --- verify phase: ONE batched full-model step scores the whole run
        # [last_tok, d_1..d_k] at positions pos..pos+k through the block
        # tables (right-padded rows: positions == -1 -> null-page writes,
        # masked attention, unread logits)
        t0 = time.time()
        S_b = _bucket(max(k_i[i] for i in act) + 1)
        toks = np.zeros((eng.batch, S_b), np.int32)
        poss = np.full((eng.batch, S_b), -1, np.int32)
        M_b = _bucket(max(len(eng.tables[i]) for i in act),
                      cap=eng.max_pages_per_req)
        bt = np.full((eng.batch, M_b), NULL_PAGE, np.int32)
        for i in act:
            n = k_i[i] + 1
            toks[i, :n] = [int(eng.last_tok[i])] + drafts[i]
            poss[i, :n] = np.arange(int(eng.pos[i]), int(eng.pos[i]) + n,
                                    dtype=np.int32)
            bt[i, :len(eng.tables[i])] = eng.tables[i]
            eng.alloc.mark_written(eng.active[i].rid, int(eng.pos[i]) + n)
        logits, eng.pages = self.verify(
            eng.params, eng.pages, jnp.asarray(toks), jnp.asarray(poss),
            jnp.asarray(bt))
        full = np.asarray(jnp.argmax(logits, -1), np.int32)  # [B, S_b]
        self.verify_time_s += time.time() - t0
        # --- acceptance: longest agreeing prefix + one full-model token
        for i in act:
            req = eng.active[i]
            g, d = full[i], drafts[i]
            m = 0
            while m < k_i[i] and g[m] == d[m]:
                m += 1
            # g[:m] matched the draft, g[m] is the bonus (full accept) or the
            # correction token -- all of them full-model argmaxes
            emitted = [int(t) for t in g[:m + 1]]
            self.accepted_tokens += m
            eng.commit(i, emitted)
            if eng.active[i] is req:  # still running: rewind speculation
                self.hist[i].extend(emitted)
                # rejected positions: rewind the main allocator's written
                # high-water to the committed length, and the draft cursor so
                # catch-up overwrites the draft cache's wrong tail
                eng.alloc.rollback(req.rid)
                self.draft_pos[i] = min(int(self.draft_pos[i]), int(eng.pos[i]))

    def stats(self) -> Dict[str, Any]:
        return {
            "policy": self.name,
            "draft_k": self.k,
            "spec_rounds": self.rounds,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "accept_rate": (self.accepted_tokens / self.drafted_tokens
                            if self.drafted_tokens else 0.0),
            "draft_time_s": round(self.draft_time_s, 4),
            "verify_time_s": round(self.verify_time_s, 4),
        }


# ---------------------------------------------------------------------------
# live weight reload


class ManifestWatcher:
    """Polls a checkpoint store's ``manifest.json`` and lands new serving
    weights by digest diff -- the train->serve hand-off channel.

    Per :meth:`poll`:

      1. ``mgr.latest()`` reads the store's current manifest -- a cheap
         atomic-file read in shared-dir mode, the coordinated candidate
         election in no-shared-FS (``local=True``) KV mode.  In KV mode both
         ``latest`` and the object gather are collectives, so every process
         of a multi-process serving job must drive its watcher at the same
         tick (``EngineCore.attach_watcher`` does).
      2. Steps already examined are skipped, as are steps whose ``params``
         tree does not structurally match the serving model: a mid-V-cycle
         checkpoint carries COALESCED (smaller-shape) params -- only
         level-0-shaped weights are servable.
      3. Each leaf's chunk-digest tuple is diffed against what the watcher
         landed last time; only CHANGED leaves are assembled and device_put
         (``CheckpointManager.assemble_diff``).  Unchanged leaves return the
         previously landed arrays -- zero bytes read, zero bytes shipped
         (``tests/test_reload.py`` pins object identity).

    The result is handed to ``EngineCore.request_reload``, which swaps at a
    tick boundary without dropping in-flight requests.
    """

    def __init__(self, mgr: CheckpointManager, like, shardings=None,
                 key: str = "params"):
        self.mgr = mgr
        self.key = key
        self.like = like
        self._flat_like = _flatten(like)
        self._flat_sh = _flatten(shardings) if shardings is not None else {}
        self.last_step = -1                # newest step actually landed
        self._seen = -1                    # newest step examined (incl. skips)
        self._sig: Dict[str, Tuple[str, ...]] = {}
        self._landed: Dict[str, Any] = {}
        self.steps_seen: List[int] = []
        self.steps_skipped: List[int] = []
        self.reload_history: List[Dict[str, Any]] = []
        self.last_reload_stats: Dict[str, Any] = {}
        self.poll_errors = 0

    def _shapes_match(self, entries) -> bool:
        if set(entries) != set(self._flat_like):
            return False
        return all(tuple(entries[k]["shape"]) ==
                   tuple(np.shape(self._flat_like[k])) for k in entries)

    def poll(self) -> Optional[Tuple[int, Any]]:
        """``(step, params)`` when new weights landed, else None."""
        m = self.mgr.latest()
        if m is None or int(m["step"]) <= self._seen:
            return None
        step = int(m["step"])
        try:
            trees = self.mgr.step_manifest(m)
            if trees is None:
                raise ValueError(
                    "live reload needs the content-addressed (v3) checkpoint "
                    "layout; this step publishes no digest manifest to diff "
                    "(saved with dedup=False?)")
            entries = trees.get(self.key, {})
            if not self._shapes_match(entries):
                self._seen = step
                self.steps_skipped.append(step)
                return None
            sig = {k: tuple(ch["digest"] for ch in rec["chunks"])
                   for k, rec in entries.items()}
            changed = sorted(k for k in sig if self._sig.get(k) != sig[k])
            flat_new = self.mgr.assemble_diff(trees, self.key, changed)
        except FileNotFoundError:
            # racing the trainer's keep-last GC: the step dir or one of its
            # objects vanished between the manifest read and assembly.  A
            # newer publish exists by definition -- catch it next poll.
            self.poll_errors += 1
            return None
        for k in changed:
            self._landed[k] = _put(flat_new[k], self._flat_like[k],
                                   self._flat_sh.get(k))
        self._sig = sig
        self._seen = self.last_step = step
        self.steps_seen.append(step)
        self.last_reload_stats = {
            "step": step, "leaves": len(sig), "changed": len(changed),
            "reused": len(sig) - len(changed),
            **{f"gather_{k}": v
               for k, v in self.mgr.last_gather_stats.items()}}
        self.reload_history.append(self.last_reload_stats)
        return step, _unflatten_into(dict(self._landed), self.like)


# ---------------------------------------------------------------------------
# scheduler core + engines


class EngineCore:
    """Engine-agnostic scheduler: request queue, admission, token commit and
    retirement are defined HERE, once.  Engines supply cache placement
    (``_place`` / ``_retire`` / ``decode_once``); the bound ``DecodePolicy``
    decides what each tick decodes."""

    engine_name = "base"

    def __init__(self, cfg, batch: int, max_seq: int,
                 policy: Optional[DecodePolicy] = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.batch = batch
        self.max_seq = max_seq
        self.params = self.model.init(jax.random.PRNGKey(0))
        self.prefill = jax.jit(make_prefill_step(self.model))
        self.pos = np.zeros((batch,), np.int32)
        self.last_tok = np.zeros((batch,), np.int32)
        self.active: List[Optional[Request]] = [None] * batch
        self.done: List[Request] = []
        self.rejected: List[Request] = []  # oversized prompts (see admit)
        self.policy = policy or GreedyPolicy()
        # hot-reload state: staged weights swap at a tick boundary once every
        # in-flight request drains (see request_reload / maybe_swap)
        self._pending_params = None
        self.reloads = 0
        self._watcher: Optional[ManifestWatcher] = None
        self._watch_every = 1
        # subclasses call self.policy.bind(self) once fully constructed

    # -- engine hooks (overridden) ------------------------------------------
    def _fits_engine(self, req: Request) -> bool:
        return True

    def _place(self, row: int, req: Request) -> Optional[int]:
        """Reserve cache space for ``req`` in ``row`` and prefill; returns the
        first generated token, or None when resources are busy right now."""
        raise NotImplementedError

    def _retire(self, row: int, req: Request) -> None:
        pass

    def _reset_engine(self) -> None:
        pass

    def _place_params(self, params):
        """Engine hook: commit reloaded params to the engine's device layout
        (the mesh-sharded paged engine device_puts onto its param
        shardings; host trees land as-is everywhere else)."""
        return params

    def _on_params_engine(self) -> None:
        """Engine hook: serving params changed.  The paged engine wipes its
        prefix cache here -- cached prompt K/V was computed under the old
        weights, and a digest commits to token content, not to the weights
        that encoded it."""

    def decode_once(self) -> np.ndarray:
        """One full-model decode step over all rows -> next-token argmaxes
        ([batch] int32; inactive rows carry garbage the caller ignores)."""
        raise NotImplementedError

    def _admit_error(self, req: Request) -> str:
        return (f"prompt of length {len(req.prompt)} cannot be admitted: "
                f"max_seq={self.max_seq} leaves no room to decode "
                f"(need len(prompt) <= max_seq - 1)")

    # -- continuous batching (shared) ---------------------------------------
    def fits(self, req: Request) -> bool:
        """The admission invariant, in ONE place: decode must be able to
        write at least one token at a valid cache index (plus any
        engine-specific capacity check)."""
        return len(req.prompt) <= self.max_seq - 1 and self._fits_engine(req)

    def admit(self, req: Request) -> bool:
        """Place ``req`` into a free row; False when rows/resources are busy
        right now.  Raises ``ValueError`` for prompts that can never fit: a
        prompt needs ``len(prompt) <= max_seq - 1`` so decode can write at
        least one token -- longer ones used to crash in cache placement
        (negative pad) or, worse, run with ``pos >= max_seq`` so the cache
        write silently dropped and decoded garbage."""
        if not self.fits(req):
            raise ValueError(self._admit_error(req))
        if self._pending_params is not None:
            # a staged weight swap drains the engine first: admitting now
            # would start this request on the OLD weights, breaking the
            # reload contract (post-reload admissions == fresh server on the
            # new weights).  The request waits at the queue head; the swap
            # happens at the next drained tick and admission resumes.
            return False
        row = next((i for i, r in enumerate(self.active) if r is None), None)
        if row is None:
            return False
        first = self._place(row, req)
        if first is None:
            return False
        self.active[row] = req
        self.pos[row] = len(req.prompt)
        self.last_tok[row] = first
        self.policy.on_admit(self, row, req)
        return True

    def commit(self, row: int, toks) -> None:
        """Append policy-accepted tokens to ``row``'s request, advancing the
        decode cursor and retiring the request the moment it is finished
        (remaining tokens, if any, are dropped -- the request is done)."""
        req = self.active[row]
        for t in toks:
            req.out.append(int(t))
            # cap at the last valid cache index: a row freed this tick must
            # never carry a pos the decode cache write would silently drop
            self.pos[row] = min(self.pos[row] + 1, self.max_seq - 1)
            self.last_tok[row] = int(t)
            self._on_token(row, req)
            if len(req.out) >= req.max_new or self.pos[row] >= self.max_seq - 1:
                self.done.append(req)
                self.active[row] = None
                self._retire(row, req)
                self.policy.on_complete(self, row, req)
                break

    def _on_token(self, row: int, req: Request) -> None:
        pass

    def step(self) -> None:
        # the tick boundary: a staged reload lands the moment the engine is
        # drained -- BEFORE the idle early-out, or a pending swap with an
        # empty engine and a waiting queue would never resolve
        self.maybe_swap()
        if not any(r is not None for r in self.active):
            return
        self.policy.tick(self)

    def run(self, requests: List[Request], max_ticks: int = 10_000) -> List[Request]:
        """Drain ``requests``: admit into free rows, decode, recycle rows.

        Oversized prompts (see :meth:`admit`) are rejected up front into
        ``self.rejected`` instead of wedging the queue head forever; a
        request that merely lacks resources *now* waits at the queue head
        for completions to free them.  An attached :class:`ManifestWatcher`
        is polled once per tick (``attach_watcher(poll_every=...)`` thins
        this): new weights are staged via :meth:`request_reload` and swap in
        at the drain boundary while the queue keeps feeding."""
        queue = list(requests)
        ticks = 0
        while (queue or any(self.active)) and ticks < max_ticks:
            if (self._watcher is not None and not self.reload_pending()
                    and ticks % self._watch_every == 0):
                got = self._watcher.poll()
                if got is not None:
                    self.request_reload(got[1])
            while queue:
                if not self.fits(queue[0]):
                    req = queue.pop(0)
                    self.rejected.append(req)
                    print(f"[serve] rejected req {req.rid}: prompt length "
                          f"{len(req.prompt)} > max_seq-1 = {self.max_seq - 1}")
                    continue
                if not self.admit(queue[0]):
                    break
                queue.pop(0)
            self.step()
            ticks += 1
        # a reload staged on the final tick still lands: the next run()
        # starts on the newest published weights
        self.maybe_swap()
        return self.done

    def reset(self) -> None:
        """Clear request state but keep params + compiled steps (bench
        reuse).  Stale cache contents are safe: every admit overwrites its
        row's range before it is read, and decode reads are position-masked."""
        self.pos[:] = 0
        self.last_tok[:] = 0
        self.active = [None] * self.batch
        self.done, self.rejected = [], []
        self._reset_engine()
        self.policy.on_reset(self)

    def set_params(self, params) -> None:
        """Hot weight swap, IMMEDIATE: in-flight rows decode their next token
        under the new weights.  The engine re-places the tree onto its device
        layout and invalidates weight-derived caches (prefix pages), then the
        policy refreshes anything derived from the serving params (the
        speculative draft projection re-runs here).  Live serving goes
        through :meth:`request_reload` instead, which defers this call to a
        drained tick boundary."""
        self.params = self._place_params(params)
        self._on_params_engine()
        self.policy.on_params(self)

    # -- live weight reload ---------------------------------------------------
    def request_reload(self, params) -> bool:
        """Stage ``params`` for a tick-boundary swap; True when the engine
        was already drained and the swap happened immediately.

        In-flight requests finish token-for-token under the weights they
        started on; new admissions wait (see :meth:`admit`) until the swap,
        so every request runs under exactly one set of weights and nothing
        is ever dropped.  Re-staging before the swap lands just replaces the
        staged tree -- only the newest weights ever swap in."""
        self._pending_params = params
        return self.maybe_swap()

    def reload_pending(self) -> bool:
        return self._pending_params is not None

    def maybe_swap(self) -> bool:
        """Land a staged reload if the engine is drained; True on swap."""
        if self._pending_params is None or any(
                r is not None for r in self.active):
            return False
        params, self._pending_params = self._pending_params, None
        self.set_params(params)
        self.reloads += 1
        return True

    def attach_watcher(self, watcher: ManifestWatcher,
                       poll_every: int = 1) -> None:
        """Drive ``watcher`` from the scheduler loop: :meth:`run` polls it
        every ``poll_every`` ticks and stages whatever it lands.  In
        no-shared-FS KV mode the poll is a collective, so every process of a
        multi-process serving job must attach with the same cadence."""
        self._watcher = watcher
        self._watch_every = max(1, poll_every)

    def stats(self) -> Dict[str, Any]:
        return dict(self.policy.stats())


class Server(EngineCore):
    """Fixed-slot engine (dense caches) -- the equivalence oracle."""

    engine_name = "slots"

    def __init__(self, cfg, batch: int = 4, max_seq: int = 128,
                 policy: Optional[DecodePolicy] = None):
        super().__init__(cfg, batch, max_seq, policy)
        self.decode = jax.jit(make_serve_step(self.model), donate_argnums=(1,))
        self.cache = zeros_cache(cfg, batch, max_seq)
        self.policy.bind(self)

    def _place(self, row: int, req: Request) -> Optional[int]:
        toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
        extras = {}
        if self.cfg.family == "vlm":
            extras["img_embeds"] = jnp.ones(
                (1, self.cfg.n_image_tokens, self.cfg.vision_dim or self.cfg.d_model),
                self.cfg.compute_dtype)
        if self.cfg.family == "audio":
            extras["enc_frames"] = jnp.ones(
                (1, self.cfg.encoder_seq, self.cfg.d_model), self.cfg.compute_dtype)
        logits, pc = self.prefill(self.params, toks,
                                  extras.get("img_embeds"), extras.get("enc_frames"))
        # pad the single-sequence cache seq dim up to max_seq and splice
        self.cache = self._splice(pc, row, len(req.prompt))
        return int(jnp.argmax(logits[0]))

    def _splice(self, prefill_cache, slot: int, prompt_len: int):
        # leaves layout: [layers, batch, ...] after scan stacking -> axis0=layers
        def one_stacked(b, s):
            if b.ndim < 3:
                return b
            if s.shape[2] != b.shape[2] and s.ndim == b.ndim and b.ndim >= 3 \
                    and s.shape[3:] == b.shape[3:]:
                pad = [(0, 0)] * s.ndim
                pad[2] = (0, b.shape[2] - s.shape[2])
                s = jnp.pad(s, pad)
            return b.at[:, slot].set(s[:, 0].astype(b.dtype))

        return jax.tree.map(one_stacked, self.cache, prefill_cache)

    def decode_once(self) -> np.ndarray:
        toks = jnp.asarray(self.last_tok)[:, None]
        pos = jnp.asarray(self.pos)
        logits, self.cache = self.decode(self.params, self.cache, toks, pos)
        return np.asarray(jnp.argmax(logits, -1), np.int32)


class PagedServer(EngineCore):
    """Paged-KV engine: block tables over a shared page pool + prefix reuse.

    Admission reserves the request's worst-case page count up front
    (``ceil(min(len(prompt)+max_new, max_seq) / page_size)``), so an admitted
    request never stalls on allocation mid-decode -- and a speculative burst
    of k+1 writes always lands inside the reserve.  Cache-hit prompts run a
    bucketed "extend" step over just the non-shared tail.
    """

    engine_name = "paged"

    def __init__(self, cfg, batch: int = 4, max_seq: int = 128,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 prefix_reuse: bool = True,
                 policy: Optional[DecodePolicy] = None,
                 mesh=None, shard_rules: Optional[Dict[str, Any]] = None):
        super().__init__(cfg, batch, max_seq, policy)
        self.page_size = page_size
        self.max_pages_per_req = -(-max_seq // page_size)
        if n_pages is None:
            # default: page-count parity with the slot engine's dense cache
            # (+1 for the reserved null page) -- admission then slot-bound
            n_pages = batch * self.max_pages_per_req + 1
        self.n_pages = n_pages
        self.paged_step = jax.jit(make_paged_decode_step(self.model),
                                  donate_argnums=(1,))
        self._write_prompt = jax.jit(
            make_write_prompt(self.model.paged_cache_specs(n_pages, page_size)),
            donate_argnums=(0,))
        self.pages = zeros_paged_cache(cfg, n_pages, page_size)
        self.alloc = BlockAllocator(n_pages, page_size, prefix_reuse=prefix_reuse)
        self.tables: List[Optional[List[int]]] = [None] * batch
        self.prefill_tokens_computed = 0
        self.mesh = mesh
        self._param_shardings = None
        if mesh is not None:
            # the serve step becomes the SAME sharded function the decode_*
            # dry-run cells compile: params on the serve layout, page pools
            # model-sharded over the kv-head axis (GQA; MLA latent pools
            # carry no head axis and replicate), tables/tokens/positions
            # replicated.  Host-side scheduling is unchanged -- only the
            # compiled step's layout is.
            from jax.sharding import NamedSharding, PartitionSpec

            from repro.distributed import mesh_ctx, put_global_tree

            psh, csh, rules = serve_shardings(self.model, mesh,
                                              n_pages=n_pages,
                                              page_size=page_size,
                                              rules=shard_rules)
            repl = NamedSharding(mesh, PartitionSpec())
            step = jax.jit(make_paged_decode_step(self.model),
                           in_shardings=(psh, csh, repl, repl, repl),
                           out_shardings=(repl, csh), donate_argnums=(1,))
            prefill = self.prefill

            # traced under the serve layout's mesh context: Pallas kernels
            # (paged decode, flash prefill) then run per shard
            def in_mesh(fn):
                def call(*args):
                    with mesh_ctx(mesh, rules):
                        return fn(*args)
                return call

            self.paged_step = in_mesh(step)
            self.prefill = in_mesh(prefill)
            self._param_shardings = psh
            self.params = put_global_tree(self.params, psh)
            self.pages = put_global_tree(self.pages, csh)
        self.policy.bind(self)

    # -- stats ---------------------------------------------------------------
    @property
    def prefill_tokens_saved(self) -> int:
        return self.alloc.reused_tokens_total

    @property
    def pages_in_use_peak(self) -> int:
        return self.alloc.pool.in_use_peak

    def stats(self) -> Dict[str, Any]:
        return {
            "pages_in_use_peak": self.pages_in_use_peak,
            "pages_capacity": self.alloc.pool.capacity,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "rolled_back_positions": self.alloc.rolled_back_total,
            **self.policy.stats(),
        }

    # -- engine hooks --------------------------------------------------------
    def _fits_engine(self, req: Request) -> bool:
        """Admissible-ever: a worst-case block table the pool could hold."""
        total = min(len(req.prompt) + req.max_new, self.max_seq)
        return self.alloc.pages_needed(total) <= self.alloc.pool.capacity

    def _admit_error(self, req: Request) -> str:
        return (f"prompt of length {len(req.prompt)} cannot be admitted: "
                f"max_seq={self.max_seq} leaves no room to decode "
                f"(need len(prompt) <= max_seq - 1 and a block table "
                f"<= {self.alloc.pool.capacity} pages)")

    def _place(self, row: int, req: Request) -> Optional[int]:
        L = len(req.prompt)
        total_positions = min(L + req.max_new, self.max_seq)
        got = self.alloc.admit(req.rid, req.prompt, total_positions)
        if got is None:
            return None
        table, reuse_len = got
        if reuse_len == 0:
            # cold prompt: the SAME prefill step as the slot engine (first
            # token bitwise-identical), then scatter its cache into our pages
            toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
            logits, pc = self.prefill(self.params, toks, None, None)
            n_pg = -(-L // self.page_size)
            ids = jnp.asarray(table[:n_pg], jnp.int32)
            self.pages = self._write_prompt(self.pages, pc, ids)
            first = int(jnp.argmax(logits[0]))
            self.prefill_tokens_computed += L
        else:
            # warm prompt: run only the tail through a bucketed extend step;
            # reused pages are read through the block table (never rewritten)
            tail = np.asarray(req.prompt[reuse_len:], np.int32)
            S = len(tail)
            S_b = _bucket(S)
            toks = np.zeros((S_b,), np.int32)
            toks[S_b - S:] = tail
            positions = np.full((S_b,), -1, np.int32)  # left-pad -> null page
            positions[S_b - S:] = np.arange(reuse_len, L, dtype=np.int32)
            M_b = _bucket(len(table), cap=self.max_pages_per_req)
            bt = np.full((M_b,), NULL_PAGE, np.int32)
            bt[:len(table)] = table
            logits, self.pages = self.paged_step(
                self.params, self.pages, jnp.asarray(toks)[None],
                jnp.asarray(positions)[None], jnp.asarray(bt)[None])
            first = int(jnp.argmax(logits[0]))
            self.prefill_tokens_computed += S
        self.tables[row] = table
        return first

    def _on_token(self, row: int, req: Request) -> None:
        self.alloc.advance(req.rid)

    def _retire(self, row: int, req: Request) -> None:
        self.tables[row] = None
        self.alloc.complete(req.rid)

    def decode_once(self) -> np.ndarray:
        act = [i for i, r in enumerate(self.active) if r is not None]
        M_b = _bucket(max(len(self.tables[i]) for i in act),
                      cap=self.max_pages_per_req)
        bt = np.full((self.batch, M_b), NULL_PAGE, np.int32)
        positions = np.full((self.batch, 1), -1, np.int32)  # idle row: len 0
        toks = np.zeros((self.batch, 1), np.int32)
        for i in act:
            bt[i, :len(self.tables[i])] = self.tables[i]
            positions[i, 0] = self.pos[i]
            toks[i, 0] = self.last_tok[i]
        logits, self.pages = self.paged_step(
            self.params, self.pages, jnp.asarray(toks),
            jnp.asarray(positions), jnp.asarray(bt))
        return np.asarray(jnp.argmax(logits, -1), np.int32)

    def _reset_engine(self) -> None:
        """Stale page contents are safe: decode reads are length-masked and
        every admit writes the prompt range of its fresh pages first."""
        self.alloc = BlockAllocator(self.n_pages, self.page_size,
                                    prefix_reuse=self.alloc.prefix is not None)
        self.tables = [None] * self.batch
        self.prefill_tokens_computed = 0

    def _place_params(self, params):
        if self._param_shardings is None:
            return params
        from repro.distributed import put_global_tree

        return put_global_tree(params, self._param_shardings)

    def _on_params_engine(self) -> None:
        self.alloc.invalidate_prefix()


POLICIES = ("greedy", "speculative")
ENGINES = ("paged", "slots")


def make_server(cfg, engine: str = "paged", batch: int = 4, max_seq: int = 128,
                page_size: int = 16, n_pages: Optional[int] = None,
                prefix_reuse: bool = True,
                policy: "str | DecodePolicy" = "greedy",
                draft_k: int = 4,
                draft_ml: Optional[MultiLevelConfig] = None,
                mesh=None):
    if isinstance(policy, str):
        if policy == "greedy":
            pol: DecodePolicy = GreedyPolicy()
        elif policy == "speculative":
            pol = SpeculativePolicy(k=draft_k, ml=draft_ml)
        else:
            raise ValueError(f"unknown policy {policy!r}; expected one of "
                             f"{POLICIES} or a DecodePolicy instance")
    elif isinstance(policy, DecodePolicy):
        pol = policy
    else:
        raise TypeError(f"policy must be one of {POLICIES} or a DecodePolicy "
                        f"instance, got {type(policy).__name__}")
    if engine == "slots":
        if mesh is not None:
            raise ValueError("mesh-sharded decode requires the paged engine "
                             "(--engine paged); the slots oracle stays "
                             "single-device")
        return Server(cfg, batch=batch, max_seq=max_seq, policy=pol)
    if engine == "paged":
        return PagedServer(cfg, batch=batch, max_seq=max_seq,
                           page_size=page_size, n_pages=n_pages,
                           prefix_reuse=prefix_reuse, policy=pol, mesh=mesh)
    raise ValueError(f"unknown engine {engine!r}; expected one of {ENGINES}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced same-family config (--no-smoke: full size)")
    ap.add_argument("--engine", choices=ENGINES, default="paged")
    ap.add_argument("--policy", choices=POLICIES, default="greedy")
    ap.add_argument("--draft-k", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--no-prefix-reuse", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="DxM ('data','model') serving mesh, e.g. 1x2 -- "
                         "paged engine only; host CPU devices are forced "
                         "when the platform has fewer (smoke/tests)")
    ap.add_argument("--reload-from", default="",
                    help="checkpoint dir to poll for live weight reloads "
                         "(a trainer's --ckpt-dir); new steps swap in at "
                         "tick boundaries without dropping in-flight "
                         "requests")
    ap.add_argument("--reload-local", action="store_true",
                    help="treat --reload-from as a per-host local dir "
                         "(no shared FS; objects gather over the KV store)")
    ap.add_argument("--poll-every", type=int, default=1,
                    help="poll the reload manifest every N scheduler ticks")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_cli_mesh

        mesh = make_cli_mesh(args.mesh)
    cfg = get_config(args.arch, smoke=args.smoke)
    srv = make_server(cfg, engine=args.engine, batch=args.batch,
                      max_seq=args.max_seq, page_size=args.page_size,
                      prefix_reuse=not args.no_prefix_reuse,
                      policy=args.policy, draft_k=args.draft_k, mesh=mesh)
    watcher = None
    if args.reload_from:
        mgr = CheckpointManager(args.reload_from, local=args.reload_local)
        watcher = ManifestWatcher(mgr, like=srv.params,
                                  shardings=getattr(srv, "_param_shardings",
                                                    None))
        srv.attach_watcher(watcher, poll_every=args.poll_every)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12)),
                    max_new=args.max_new) for i in range(args.requests)]
    t0 = time.time()
    done = srv.run(reqs)
    dt = time.time() - t0
    tok = sum(len(r.out) for r in done)
    print(f"[serve] engine={args.engine} policy={args.policy}: {len(done)} "
          f"requests, {tok} tokens in {dt:.1f}s "
          f"({tok/max(dt,1e-9):.1f} tok/s, batch={args.batch})")
    print(f"[serve] {srv.stats()}")
    if watcher is not None:
        print(f"[serve] reloads={srv.reloads} steps_seen={watcher.steps_seen} "
              f"steps_skipped={watcher.steps_skipped} "
              f"last={watcher.last_reload_stats}")
    for r in done[:3]:
        print(f"  req {r.rid}: prompt[:4]={r.prompt[:4].tolist()} -> out[:8]={r.out[:8]}")


if __name__ == "__main__":
    main()
