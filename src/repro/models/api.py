"""Public model facade + step builders (train / prefill / decode).

``Model`` wraps a ModelConfig with spec/init/loss/forward entry points used by
the V-cycle runner, the baselines, the launcher and the dry-run.  Step builders
return pure functions suitable for ``jax.jit`` (and ``.lower().compile()``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, TrainConfig
from repro.models import lm as lm_lib
from repro.models import vit as vit_lib
from repro.optim import adamw_init, adamw_init_specs, adamw_update
from repro.param import init_tree


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # -- specs / init ------------------------------------------------------
    def specs(self):
        if self.cfg.family == "vit":
            return vit_lib.vit_specs(self.cfg)
        return lm_lib.lm_specs(self.cfg)

    def cache_specs(self, batch: int, max_seq: int):
        return lm_lib.cache_specs(self.cfg, batch, max_seq)

    def paged_cache_specs(self, n_pages: int, page_size: int):
        return lm_lib.paged_cache_specs(self.cfg, n_pages, page_size)

    def init(self, key: jax.Array):
        return init_tree(key, self.specs(), dtype=self.cfg.param_dtype)

    def projection_plan(self, ml=None, *, width: bool = True,
                        depth: bool = True):
        """This model's :class:`~repro.core.plans.ProjectionPlan` for one
        level transition: the family contract the V-cycle, baselines and the
        serving draft projection all share (coalescible axes, protected axes,
        role overrides, carried MoE scalars, ``small_cfg``)."""
        from repro.core.plans import build_plan

        return build_plan(self.cfg, ml, width=width, depth=depth)

    # -- losses ------------------------------------------------------------
    def loss(self, params, batch: Dict[str, jax.Array], z_loss: float = 0.0):
        cfg = self.cfg
        if cfg.family == "vit":
            logits = vit_lib.vit_forward(params, batch["patches"], cfg)
            return vit_lib.vit_loss(logits, batch["labels"])
        out = lm_lib.lm_forward(
            params, batch["tokens"], cfg, mode="train",
            img_embeds=batch.get("img_embeds"), enc_frames=batch.get("enc_frames"))
        mtp_labels = None
        if cfg.mtp_depth:
            lbl = batch["labels"]
            mtp_labels = jnp.concatenate(
                [lbl[:, 1:], jnp.full_like(lbl[:, :1], -1)], axis=1)
        with jax.named_scope("loss"):
            return lm_lib.lm_loss(out["logits"], batch["labels"], cfg, out["aux"],
                                  out.get("mtp_logits"), mtp_labels, z_loss)

    def forward_logits(self, params, batch):
        if self.cfg.family == "vit":
            return vit_lib.vit_forward(params, batch["patches"], self.cfg)
        return lm_lib.lm_forward(params, batch["tokens"], self.cfg, mode="train",
                                 img_embeds=batch.get("img_embeds"),
                                 enc_frames=batch.get("enc_frames"))["logits"]


def build_model(cfg: ModelConfig) -> Model:
    if cfg.kernel_backend:
        # fail fast on a typo'd backend instead of mid-training at trace time
        from repro.kernels import dispatch as kdispatch

        kdispatch.validate_backend(cfg.kernel_backend)
    return Model(cfg)


# ---------------------------------------------------------------------------
# step builders


def make_train_step(model: Model, tc: TrainConfig, *, grad_reduce=None,
                    mesh=None) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    With ``tc.grad_accum > 1`` the batch leaves must have a leading microbatch
    axis of size grad_accum; gradients are accumulated with a scan (activation
    memory divided by grad_accum — the standard TPU pipelining lever).

    With a ``grad_reduce`` strategy (``distributed/reduce.py``) and a ``mesh``,
    the step is instead built as a ``shard_map`` over the mesh with gradient
    reduction an explicit, pluggable layer, and the signature becomes 4-ary:
    ``train_step(params, opt_state, ef, batch) -> (params, opt_state, ef,
    metrics)`` where ``ef`` is the strategy's carried state (the EF residual
    tree for int8, ``None``-leaved zeros tree for stateless strategies).
    """
    if grad_reduce is not None:
        if mesh is None:
            raise ValueError("grad_reduce requires a mesh")
        return _make_shardmap_train_step(model, tc, grad_reduce, mesh)

    def loss_fn(params, micro):
        loss, metrics = model.loss(params, micro, z_loss=tc.z_loss)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    # Per-step FSDP weight pre-gather (MaxText-style): cast the f32 master
    # params to compute dtype ONCE per step with the data-axis sharding
    # dropped -- the all-gather then happens outside the grad-accum loop
    # instead of once per layer *per microbatch* (EXPERIMENTS.md §Perf
    # qwen3-14b iter).  The VJP of the constraint+cast is exactly the f32
    # gradient reduce-scatter back onto the FSDP layout.  Opt-in per arch:
    # the per-device gathered copy is total_bf16/model_shard, too large for
    # the 400B+ models (they keep per-layer gathering).
    if tc.pregather_params:
        from repro.distributed import shard_l
        from repro.param import axes_tree

        p_axes = axes_tree(model.specs())
        no_fsdp = {"embed": None, "embed_cat2": None}

        def pregather(params):
            return jax.tree.map(
                lambda p, ax: shard_l(p.astype(model.cfg.compute_dtype), ax, no_fsdp),
                params, p_axes)
    else:
        pregather = lambda params: params

    def train_step(params, opt_state, batch):
        if tc.pregather_params:
            p_use, pull = jax.vjp(pregather, params)
        else:
            p_use, pull = params, None

        if tc.grad_accum > 1:
            def acc_body(carry, micro):
                g_acc, m_acc = carry
                (_, metrics), grads = grad_fn(p_use, micro)
                g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, grads)
                m_acc = jax.tree.map(lambda a, b: a + b, m_acc, metrics)
                return (g_acc, m_acc), None

            (_, m0), g0 = grad_fn(p_use, jax.tree.map(lambda x: x[0], batch))
            g0 = jax.tree.map(lambda g: g.astype(jnp.float32), g0)
            rest = jax.tree.map(lambda x: x[1:], batch)
            (g_sum, m_sum), _ = jax.lax.scan(acc_body, (g0, m0), rest)
            inv = 1.0 / tc.grad_accum
            grads = jax.tree.map(lambda g: g * inv, g_sum)
            metrics = jax.tree.map(lambda m: m * inv, m_sum)
        else:
            (_, metrics), grads = grad_fn(p_use, batch)
        if pull is not None:
            # one reduce-scatter back onto the FSDP layout per step
            grads = pull(jax.tree.map(
                lambda g, p: g.astype(p.dtype), grads, p_use))[0]
        params, opt_state, om = adamw_update(params, grads, opt_state, tc)
        return params, opt_state, {**metrics, **om}

    return train_step


def _make_shardmap_train_step(model: Model, tc: TrainConfig, grad_reduce, mesh):
    """The explicit-reduction train step: grad accumulation + reduction run
    inside a ``shard_map`` over ``mesh`` with the strategy injected.

    Params/opt enter the body replicated (in_specs P()): under ``jit`` with
    FSDP in_shardings this inserts exactly one all-gather per step — the same
    pattern ``tc.pregather_params`` opts into on the pjit path, so that flag is
    ignored here.  The optimizer update runs redundantly per rank on the
    replicated reduced gradients (identical values everywhere, so the
    global-norm clip stays consistent); jit out_shardings re-shard the result
    back onto the FSDP layout, keeping the external train-state layout — and
    hence checkpoints and V-cycle level transitions — unchanged.  Compute over
    the "model" axis is replicated inside the body (tensor parallelism stays a
    pjit concern; this path targets the data/DCN reduction).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import no_constraints
    from repro.distributed.sharding import logical_spec

    def loss_fn(params, micro):
        loss, metrics = model.loss(params, micro, z_loss=tc.z_loss)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    data_axes = grad_reduce.data_axes

    def body(params, opt_state, ef, batch):
        with no_constraints():
            if tc.grad_accum > 1:
                def acc_body(carry, micro):
                    g_acc, m_acc = carry
                    (_, metrics), grads = grad_fn(params, micro)
                    g_acc = jax.tree.map(
                        lambda a, b: a + b.astype(a.dtype), g_acc, grads)
                    m_acc = jax.tree.map(lambda a, b: a + b, m_acc, metrics)
                    return (g_acc, m_acc), None

                (_, m0), g0 = grad_fn(params, jax.tree.map(lambda x: x[0], batch))
                g0 = jax.tree.map(lambda g: g.astype(jnp.float32), g0)
                rest = jax.tree.map(lambda x: x[1:], batch)
                (g_sum, m_sum), _ = jax.lax.scan(acc_body, (g0, m0), rest)
                inv = 1.0 / tc.grad_accum
                grads = jax.tree.map(lambda g: g * inv, g_sum)
                metrics = jax.tree.map(lambda m: m * inv, m_sum)
            else:
                (_, metrics), grads = grad_fn(params, batch)
        grads, ef = grad_reduce.reduce(grads, ef)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, data_axes), metrics)
        params, opt_state, om = adamw_update(params, grads, opt_state, tc)
        return params, opt_state, ef, {**metrics, **om}

    ef_spec = grad_reduce.state_specs() if grad_reduce.stateful else P()

    def train_step(params, opt_state, ef, batch):
        # specs are computed at trace time from the actual abstract shapes so
        # the batch specs agree leaf-for-leaf with ``batch_shardings`` (same
        # progressive-drop divisibility logic)
        pspec = jax.tree.map(lambda _: P(), params)
        ospec = jax.tree.map(lambda _: P(), opt_state)
        efspec = jax.tree.map(lambda _: ef_spec, ef)

        def bspec_one(x):
            axes = ("batch",) + ("seq",) * (len(x.shape) - 1)
            return logical_spec(x.shape, axes, mesh)

        bspec = jax.tree.map(bspec_one, batch)
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, ospec, efspec, bspec),
            out_specs=(pspec, ospec, efspec, P()),
            check_vma=False)
        return f(params, opt_state, ef, batch)

    return train_step


def make_eval_loss(model: Model) -> Callable:
    def eval_loss(params, batch):
        loss, metrics = model.loss(params, batch)
        return metrics

    return eval_loss


def make_prefill_step(model: Model) -> Callable:
    """prefill_step(params, tokens, [extras]) -> (last_logits, caches)."""
    cfg = model.cfg

    def prefill_step(params, tokens, img_embeds=None, enc_frames=None):
        out = lm_lib.lm_forward(params, tokens, cfg, mode="prefill",
                                img_embeds=img_embeds, enc_frames=enc_frames)
        return out["logits"][:, -1, :], out["caches"]

    return prefill_step


def make_serve_step(model: Model) -> Callable:
    """serve_step(params, caches, tokens [B,1], pos [B]) -> (logits, caches).

    One new token against a KV/state cache of ``max_seq`` (the decode_* and
    long_* assigned shapes lower exactly this function).
    """
    cfg = model.cfg

    def serve_step(params, caches, tokens, pos):
        positions = pos[:, None]
        out = lm_lib.lm_forward(params, tokens, cfg, positions=positions,
                                mode="decode", caches=caches)
        return out["logits"][:, -1, :], out["caches"]

    return serve_step


def make_paged_decode_step(model: Model) -> Callable:
    """step(params, pages, tokens [B,S], positions [B,S], block_tables [B,M])
    -> (last_logits, pages).

    Decode/extend against the shared page pool: each batch row reads and
    writes K/V through its block-table row, so cost scales with the pages a
    request actually occupies, not ``max_seq``.  S==1 is the batched decode
    step; S>1 is the prefix-reuse "extend" step (left-padded rows carry
    positions == -1, which ``paged_write`` routes to the reserved null page).
    """
    cfg = model.cfg

    def paged_decode_step(params, pages, tokens, positions, block_tables):
        out = lm_lib.lm_forward(params, tokens, cfg, positions=positions,
                                mode="decode", caches=pages,
                                block_tables=block_tables)
        return out["logits"][:, -1, :], out["caches"]

    return paged_decode_step


def make_verify_step(model: Model) -> Callable:
    """verify_step(params, pages, tokens [B,S], positions [B,S], block_tables
    [B,M]) -> (logits [B,S,V], pages).

    The speculative-decode verifier: identical forward to
    ``make_paged_decode_step`` (same paged reads/writes through the block
    table) but returning logits at *every* position, so one batched
    full-model step scores a drafted token run d_0..d_k written at positions
    p..p+k.  ``logits[:, i]`` is the full model's next-token distribution
    after the token at ``positions[:, i]`` -- the acceptance rule compares
    ``argmax(logits[:, i])`` against the draft's proposal for position
    ``p+i+1``, and the first disagreement's argmax doubles as the correction
    token, which is what makes greedy speculative decoding lossless.
    Right-padded rows carry ``positions == -1`` (writes routed to the null
    page, attention fully masked); their logits are garbage and unread.
    """
    cfg = model.cfg

    def verify_step(params, pages, tokens, positions, block_tables):
        out = lm_lib.lm_forward(params, tokens, cfg, positions=positions,
                                mode="decode", caches=pages,
                                block_tables=block_tables)
        return out["logits"], out["caches"]

    return verify_step


def init_train_state(model: Model, tc: TrainConfig, key: jax.Array):
    params = model.init(key)
    opt_state = adamw_init(params, tc)
    return params, opt_state


def train_state_specs(model: Model, tc: TrainConfig):
    ps = model.specs()
    return ps, adamw_init_specs(ps, tc)


def serve_shardings(model: Model, mesh, *, n_pages=None, page_size=None,
                    rules=None):
    """(params, page-pool) NamedSharding trees + merged rules for mesh-sharded
    serving on ``mesh``.

    Layout: the training ``RULES`` overlaid with ``SERVE_RULES`` (read-only
    params spread over every device, no FSDP/DP gather per step) plus
    ``cache_kv_heads -> "model"``, so a GQA page pool shards its K/V heads
    over the model axis while MLA's latent ``ckv``/``kpe`` pools (no head
    axis) and the block tables stay replicated.  The page-pool tree is None
    unless ``n_pages``/``page_size`` are given.  The merged rule dict is
    returned too so callers can enter ``mesh_ctx`` with the identical layout
    (the serve step is then the same sharded function the ``decode_*``
    dry-run cells compile).
    """
    from repro.distributed import param_shardings
    from repro.distributed.sharding import RULES, SERVE_RULES

    merged = dict(RULES)
    merged.update(SERVE_RULES)
    merged["cache_kv_heads"] = "model"
    merged.update(rules or {})
    psh = param_shardings(model.specs(), mesh, merged)
    csh = None
    if n_pages is not None:
        csh = param_shardings(
            model.paged_cache_specs(n_pages, page_size), mesh, merged)
    return psh, csh, merged


def train_state_shardings(model: Model, tc: TrainConfig, mesh, rules=None,
                          grad_reduce=None):
    """(param, opt) NamedSharding trees for a model's train state on ``mesh``.

    Derived from the Spec trees (the optimizer mirrors the parameter logical
    axes), so every V-cycle level gets its own layout and a checkpoint written
    under one mesh can be restored onto another by passing these to
    ``CheckpointManager.restore(shardings=...)``.

    With a stateful ``grad_reduce`` strategy a third tree is returned: the
    sharding of the strategy's carried state (EF residuals, DCN-axis sharded
    on their leading dim).
    """
    from repro.distributed import param_shardings

    ps, opt_specs = train_state_specs(model, tc)
    psh = param_shardings(ps, mesh, rules)
    osh = param_shardings(opt_specs, mesh, rules)
    if grad_reduce is None:
        return psh, osh
    efsh = (grad_reduce.state_shardings(psh, mesh)
            if grad_reduce.stateful else None)
    return psh, osh, efsh


def zero_train_state(model: Model, tc: TrainConfig, grad_reduce=None):
    """Zero-filled (params, opt_state) with the exact structure/shape/dtype of
    ``init_train_state`` -- cheap "like" trees for checkpoint restore (no RNG,
    no init math, no model trace).  With a stateful ``grad_reduce`` strategy a
    third tree (the zero EF-residual state) is returned."""
    from repro.param import is_spec

    ps, opt_specs = train_state_specs(model, tc)
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype or model.cfg.param_dtype),
        ps, is_leaf=is_spec)
    opt_state = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), opt_specs, is_leaf=is_spec)
    if grad_reduce is None:
        return params, opt_state
    ef = grad_reduce.init_state(params) if grad_reduce.stateful else None
    return params, opt_state, ef
