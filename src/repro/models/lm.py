"""Unified stage-based transformer covering every assigned family:

dense / MoE decoder LMs, hybrid Mamba+attention (Jamba), xLSTM, VLM decoders
with gated cross-attention (Llama-3.2-Vision), and encoder-decoder audio
(Whisper).  Encoder-only (BERT proxy) and ViT reuse the same blocks.

Parameters are stacked per stage-pattern position with a leading "layers"
axis and the forward scans over ``repeats`` -- compact HLO at 61-72 layers and
the axis the paper's depth-coalescing operator acts on.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import BlockSpec, ModelConfig, Stage
from repro.distributed import shard_l
from repro.layers import attention as attn
from repro.layers import ffn as ffn_lib
from repro.layers import ssm
from repro.layers.basic import embed_specs, embed_tokens, norm_apply, norm_specs, unembed
from repro.param import Spec

# ---------------------------------------------------------------------------
# per-block specs


def _stack(tree, n: int):
    def one(s: Spec) -> Spec:
        return Spec((n,) + s.shape, ("layers",) + s.axes, ("-",) + s.roles,
                    init=s.init, scale=s.scale, dtype=s.dtype)

    return jax.tree.map(one, tree, is_leaf=lambda x: isinstance(x, Spec))


def block_specs(cfg: ModelConfig, bs: BlockSpec) -> Dict[str, Any]:
    s: Dict[str, Any] = {}
    mixer = bs.mixer
    if mixer in ("attn", "enc_attn", "dec_attn"):
        s["norm1"] = norm_specs(cfg)
        s["mixer"] = attn.mla_specs(cfg) if cfg.attn_type == "mla" else attn.gqa_specs(cfg)
        if mixer == "dec_attn":
            s["norm_x"] = norm_specs(cfg)
            s["cross"] = attn.cross_attn_specs(cfg, kv_axis="embed")
    elif mixer == "cross_attn":
        s["norm1"] = norm_specs(cfg)
        s["mixer"] = attn.cross_attn_specs(cfg, kv_axis="vision_embed",
                                           kv_dim=cfg.vision_dim or cfg.d_model)
    elif mixer == "mamba":
        s["norm1"] = norm_specs(cfg)
        s["mixer"] = ssm.mamba_specs(cfg)
    elif mixer == "mlstm":
        s["norm1"] = norm_specs(cfg)
        s["mixer"] = ssm.mlstm_specs(cfg)
    elif mixer == "slstm":
        s["norm1"] = norm_specs(cfg)
        s["mixer"] = ssm.slstm_specs(cfg)
    else:
        raise ValueError(f"unknown mixer {mixer}")
    if bs.ffn == "dense":
        s["norm2"] = norm_specs(cfg)
        s["ffn"] = ffn_lib.ffn_specs(cfg)
    elif bs.ffn == "moe":
        s["norm2"] = norm_specs(cfg)
        s["ffn"] = ffn_lib.moe_specs(cfg)
    return s


def block_cache_specs(cfg: ModelConfig, bs: BlockSpec, batch: int, max_seq: int,
                      n_cross_tokens: int = 0) -> Dict[str, Any]:
    c: Dict[str, Any] = {}
    mixer = bs.mixer
    if mixer in ("attn", "dec_attn"):
        c["self"] = (attn.mla_cache_specs(cfg, batch, max_seq) if cfg.attn_type == "mla"
                     else attn.gqa_cache_specs(cfg, batch, max_seq))
        if mixer == "dec_attn":
            c["cross"] = attn.cross_kv_cache_specs(cfg, batch, n_cross_tokens)
    elif mixer == "cross_attn":
        c["cross"] = attn.cross_kv_cache_specs(cfg, batch, n_cross_tokens)
    elif mixer == "mamba":
        c["ssm"] = ssm.mamba_cache_specs(cfg, batch)
    elif mixer == "mlstm":
        c["ssm"] = ssm.mlstm_cache_specs(cfg, batch)
    elif mixer == "slstm":
        c["ssm"] = ssm.slstm_cache_specs(cfg, batch)
    return c


def paged_block_cache_specs(cfg: ModelConfig, bs: BlockSpec, n_pages: int,
                            page_size: int) -> Dict[str, Any]:
    """Block-table layout for the serving page pool.  Only pure self-attention
    blocks page cleanly: SSM state is O(1) (nothing to page) and cross/enc-dec
    K/V is request-global, so those families stay on the slot engine."""
    if bs.mixer != "attn":
        raise NotImplementedError(
            f"paged KV serving supports mixer 'attn' only, got {bs.mixer!r} "
            "(use --engine slots)")
    return {"self": (attn.mla_paged_cache_specs(cfg, n_pages, page_size)
                     if cfg.attn_type == "mla"
                     else attn.gqa_paged_cache_specs(cfg, n_pages, page_size))}


# ---------------------------------------------------------------------------
# per-block apply


def block_apply(
    p: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    bs: BlockSpec,
    *,
    positions: jax.Array,
    mode: str,  # train | prefill | decode
    cache: Optional[Dict] = None,  # required for decode; ignored otherwise
    cross_src: Optional[jax.Array] = None,  # image embeds / encoder output
    block_tables: Optional[jax.Array] = None,  # [B,M]: decode cache is paged
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Returns (x, new_cache, moe_aux).  new_cache is None in train mode,
    freshly created in prefill mode, updated in decode mode."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    mixer = bs.mixer
    decode = mode == "decode"
    prefill = mode == "prefill"

    with jax.named_scope("ssm" if mixer in ("mamba", "mlstm", "slstm") else "attention"):
        if mixer in ("attn", "enc_attn", "dec_attn"):
            h = norm_apply(p["norm1"], x, cfg)
            causal = mixer != "enc_attn"
            self_cache = cache.get("self") if decode else None
            if cfg.attn_type == "mla":
                y, c_new = attn.mla_apply(p["mixer"], h, cfg, positions=positions,
                                          causal=causal, cache=self_cache,
                                          block_tables=block_tables)
            else:
                y, c_new = attn.gqa_apply(p["mixer"], h, cfg, positions=positions,
                                          causal=causal, cache=self_cache,
                                          block_tables=block_tables)
            x = x + y
            if prefill:
                new_cache["self"] = _prefill_self_cache(p["mixer"], h, cfg, positions)
            elif decode:
                new_cache["self"] = c_new
            if mixer == "dec_attn":
                hx = norm_apply(p["norm_x"], x, cfg)
                kv_cache = cache.get("cross") if decode else None
                y = attn.cross_attn_apply(p["cross"], hx, cfg, kv_src=cross_src,
                                          kv_cache=kv_cache, gated=False)
                x = x + y
                if prefill:
                    new_cache["cross"] = attn.cross_attn_precompute(p["cross"], cross_src, cfg)
                elif decode:
                    new_cache["cross"] = cache["cross"]
        elif mixer == "cross_attn":
            h = norm_apply(p["norm1"], x, cfg)
            kv_cache = cache.get("cross") if decode else None
            y = attn.cross_attn_apply(p["mixer"], h, cfg, kv_src=cross_src,
                                      kv_cache=kv_cache, gated=True)
            x = x + y
            if prefill:
                new_cache["cross"] = attn.cross_attn_precompute(p["mixer"], cross_src, cfg)
            elif decode:
                new_cache["cross"] = cache["cross"]
        elif mixer in ("mamba", "mlstm", "slstm"):
            h = norm_apply(p["norm1"], x, cfg)
            fn = {"mamba": ssm.mamba_apply, "mlstm": ssm.mlstm_apply,
                  "slstm": ssm.slstm_apply}[mixer]
            ssm_cache = cache.get("ssm") if decode else None
            y, c_new = fn(p["mixer"], h, cfg, cache=ssm_cache, return_state=prefill)
            if prefill or decode:
                new_cache["ssm"] = c_new
            x = x + y
        else:
            raise ValueError(mixer)

    if bs.ffn == "dense":
        with jax.named_scope("mlp"):
            h = norm_apply(p["norm2"], x, cfg)
            x = x + ffn_lib.ffn_apply(p["ffn"], h, cfg)
    elif bs.ffn == "moe":
        with jax.named_scope("moe"):
            h = norm_apply(p["norm2"], x, cfg)
            y, a = ffn_lib.moe_apply(p["ffn"], h, cfg)
            x = x + y
            aux = aux + a
    return x, (new_cache if (prefill or decode) else None), aux


def _prefill_self_cache(p: Dict, h: jax.Array, cfg: ModelConfig, positions) -> Dict:
    """Recompute the (cheap, linear) K/V projections to fill the decode cache
    after a prefill forward.  For MLA this is the compressed latent cache."""
    from repro.layers.basic import apply_rope, rms_norm

    cdt = cfg.compute_dtype
    if cfg.attn_type == "mla":
        ckv = rms_norm(jnp.einsum("bse,el->bsl", h, p["wkv_a"].astype(cdt)),
                       p["kv_norm"], cfg.norm_eps)
        kpe = apply_rope(jnp.einsum("bse,er->bsr", h, p["wk_rope"].astype(cdt))[:, :, None, :],
                         positions, cfg.rope_theta)[:, :, 0, :]
        return {"ckv": shard_l(ckv, ("batch", "cache_seq", "kv_lora")),
                "kpe": shard_l(kpe, ("batch", "cache_seq", "rope_dim"))}
    k = jnp.einsum("bse,ehd->bshd", h, p["wk"].astype(cdt))
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"].astype(cdt))
    if cfg.use_bias:
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    k = apply_rope(k, positions, cfg.rope_theta)
    return {"k": shard_l(k, ("batch", "cache_seq", "cache_kv_heads", "head_dim")),
            "v": shard_l(v, ("batch", "cache_seq", "cache_kv_heads", "head_dim"))}


# ---------------------------------------------------------------------------
# whole-model specs


def encoder_stages(cfg: ModelConfig) -> Tuple[Stage, ...]:
    if not cfg.n_encoder_layers:
        return ()
    return (Stage((BlockSpec("enc_attn", "dense"),), cfg.n_encoder_layers),)


def lm_specs(cfg: ModelConfig) -> Dict[str, Any]:
    s: Dict[str, Any] = {"embed": embed_specs(cfg)}
    s["stages"] = {
        f"stage_{i}": {
            f"b{j}": _stack(block_specs(cfg, bsj), st.repeats)
            for j, bsj in enumerate(st.pattern)
        }
        for i, st in enumerate(cfg.stages)
    }
    s["final_norm"] = norm_specs(cfg)
    if cfg.n_encoder_layers:
        s["encoder"] = {
            "stages": {
                f"stage_{i}": {
                    f"b{j}": _stack(block_specs(cfg, bsj), st.repeats)
                    for j, bsj in enumerate(st.pattern)
                }
                for i, st in enumerate(encoder_stages(cfg))
            },
            "final_norm": norm_specs(cfg),
        }
    if cfg.mtp_depth:
        s["mtp"] = {
            "proj": Spec((2 * cfg.d_model, cfg.d_model), ("embed_cat2", "embed"), ("in", "out"),
                         init="fan_in"),
            "norm_h": norm_specs(cfg),
            "norm_e": norm_specs(cfg),
            "block": block_specs(cfg, BlockSpec("attn", "dense")),
            "final_norm": norm_specs(cfg),
        }
    return s


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> Dict[str, Any]:
    n_cross = cfg.n_image_tokens or cfg.encoder_seq
    return {
        f"stage_{i}": {
            f"b{j}": _stack(block_cache_specs(cfg, bsj, batch, max_seq, n_cross), st.repeats)
            for j, bsj in enumerate(st.pattern)
        }
        for i, st in enumerate(cfg.stages)
    }


def paged_cache_specs(cfg: ModelConfig, n_pages: int, page_size: int) -> Dict[str, Any]:
    """Whole-model page-pool specs: one ``[n_pages, page_size, ...]`` pool per
    stacked layer leaf, shared across requests via per-request block tables.

    Works for any config with attention-only mixers -- including the
    *coalesced* level-1 config, which is how the speculative decode policy
    builds its draft cache: ``paged_cache_specs(coalesce_config(cfg, ml),
    ...)`` gives the half-width pool the drafted tokens stream through
    (``launch/serve.py::SpeculativePolicy``)."""
    return {
        f"stage_{i}": {
            f"b{j}": _stack(paged_block_cache_specs(cfg, bsj, n_pages, page_size),
                            st.repeats)
            for j, bsj in enumerate(st.pattern)
        }
        for i, st in enumerate(cfg.stages)
    }


# ---------------------------------------------------------------------------
# forward


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def run_stages(
    params: Dict,
    stages: Tuple[Stage, ...],
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    mode: str,
    caches: Optional[Dict] = None,  # decode: input caches; prefill: created fresh
    cross_src: Optional[jax.Array] = None,
    block_tables: Optional[jax.Array] = None,  # [B,M]: caches are page pools
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}
    want_cache = mode in ("prefill", "decode")
    for i, st in enumerate(stages):
        p_st = params[f"stage_{i}"]
        c_st = caches.get(f"stage_{i}") if (caches is not None and mode == "decode") else None

        def body(carry, xs, st=st):
            xx, aux = carry
            p_sl, c_sl = xs
            c_out = {}
            for j, bsj in enumerate(st.pattern):
                cj = c_sl.get(f"b{j}") if c_sl is not None else None
                xx, c_new, a = block_apply(p_sl[f"b{j}"], xx, cfg, bsj,
                                           positions=positions, mode=mode,
                                           cache=cj, cross_src=cross_src,
                                           block_tables=block_tables)
                if c_new is not None:
                    c_out[f"b{j}"] = c_new
                aux = aux + a
            return (xx, aux), (c_out if c_out else 0)

        body = _remat_wrap(body, cfg)
        (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), (p_st, c_st))
        if want_cache:
            new_caches[f"stage_{i}"] = ys
    return x, (new_caches if want_cache else None), aux_total


def lm_forward(
    params: Dict,
    tokens: jax.Array,  # [B,S] int32
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,  # [B,S]; default arange
    mode: str = "train",
    caches: Optional[Dict] = None,
    img_embeds: Optional[jax.Array] = None,  # [B,N,E] (vlm stub frontend)
    enc_frames: Optional[jax.Array] = None,  # [B,T,E] (audio stub frontend)
    enc_out: Optional[jax.Array] = None,  # precomputed encoder output (decode)
    # [B,M]: decode caches are paged.  S==1 is batched decode; S>1 with
    # explicit positions is the multi-token paged step shared by the
    # prefix-reuse "extend" path and the speculative verify step (logits at
    # every position score a drafted run; positions == -1 mark padding --
    # writes land on the null page and attention is fully masked).
    block_tables: Optional[jax.Array] = None,
) -> Dict[str, Any]:
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    with jax.named_scope("embed"):
        x = embed_tokens(params["embed"], tokens, cfg)
        x = shard_l(x, ("batch", "seq", "act_embed"))

    cross_src = None if img_embeds is None else img_embeds.astype(cfg.compute_dtype)
    if cfg.n_encoder_layers and mode != "decode":  # decode reads cross K/V from cache
        if enc_out is None:
            assert enc_frames is not None, "encoder-decoder needs enc_frames or enc_out"
            e = shard_l(enc_frames.astype(cfg.compute_dtype), ("batch", "enc_seq", "act_embed"))
            e_pos = jnp.broadcast_to(jnp.arange(e.shape[1])[None], (B, e.shape[1]))
            e, _, _ = run_stages(params["encoder"]["stages"], encoder_stages(cfg), e, cfg,
                                 positions=e_pos, mode="train")
            enc_out = norm_apply(params["encoder"]["final_norm"], e, cfg)
        cross_src = enc_out

    x, new_caches, aux = run_stages(params["stages"], cfg.stages, x, cfg,
                                    positions=positions, mode=mode, caches=caches,
                                    cross_src=cross_src, block_tables=block_tables)
    with jax.named_scope("head"):
        x = norm_apply(params["final_norm"], x, cfg)
        logits = unembed(params["embed"], x, cfg)
        logits = shard_l(logits, ("batch", "seq", "act_vocab"))
    out = {"logits": logits, "aux": aux, "caches": new_caches, "enc_out": enc_out}

    if cfg.mtp_depth and mode == "train":
        # DeepSeek-V3 multi-token prediction: one extra block predicting t+2
        # from [h_t ; emb(token_{t+1})].
        mp = params["mtp"]
        emb_next = embed_tokens(params["embed"], jnp.roll(tokens, -1, axis=1), cfg)
        hcat = jnp.concatenate([norm_apply(mp["norm_h"], x, cfg),
                                norm_apply(mp["norm_e"], emb_next, cfg)], axis=-1)
        h2 = jnp.einsum("bsf,fe->bse", hcat, mp["proj"].astype(cfg.compute_dtype))
        h2, _, _ = block_apply(mp["block"], h2, cfg, BlockSpec("attn", "dense"),
                               positions=positions, mode="train")
        h2 = norm_apply(mp["final_norm"], h2, cfg)
        out["mtp_logits"] = unembed(params["embed"], h2, cfg)
    return out


# ---------------------------------------------------------------------------
# losses


def lm_loss(
    logits: jax.Array,  # [B,S,V]
    labels: jax.Array,  # [B,S] int32, -1 = ignore
    cfg: ModelConfig,
    aux: jax.Array = 0.0,
    mtp_logits: Optional[jax.Array] = None,
    mtp_labels: Optional[jax.Array] = None,
    z_loss: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    def ce(lg, lb):
        # vocab-sharding-friendly CE: take_along_axis over the model-sharded
        # vocab axis would force an f32 logits all-gather (GBs per device at
        # 152k vocab; EXPERIMENTS.md §Perf iter.3).  A one-hot contraction
        # keeps the vocab axis sharded end-to-end (Megatron-style loss).
        lg = lg.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        onehot = jax.nn.one_hot(jnp.maximum(lb, 0), lg.shape[-1], dtype=jnp.float32)
        onehot = shard_l(onehot, ("batch", "seq", "act_vocab"))
        ll = jnp.einsum("bsv,bsv->bs", lg, onehot)
        mask = (lb >= 0).astype(jnp.float32)
        nll = (lse - ll) * mask
        zl = z_loss * jnp.square(lse) * mask if z_loss else 0.0
        return jnp.sum(nll + zl) / jnp.maximum(jnp.sum(mask), 1.0)

    loss = ce(logits, labels)
    metrics = {"ce": loss}
    if mtp_logits is not None and mtp_labels is not None:
        mtp = ce(mtp_logits, mtp_labels)
        loss = loss + cfg.mtp_loss_weight * mtp
        metrics["mtp_ce"] = mtp
    if cfg.n_experts:
        loss = loss + cfg.router_aux_coef * aux
        metrics["moe_aux"] = aux
    metrics["loss"] = loss
    return loss, metrics
