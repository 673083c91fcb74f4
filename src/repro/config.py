"""Configuration system: model / blocks / shapes / training / multilevel / mesh.

One ``ModelConfig`` covers every assigned architecture family (dense, MoE, MLA,
hybrid Mamba+attention, xLSTM, VLM cross-attention, encoder-decoder audio).
Depth is described by *stages*: each stage is a short heterogeneous ``pattern``
of blocks scanned over ``repeats`` (compact HLO for 61-72 layer dry-runs, and
the axis along which the paper's depth-coalescing operator acts).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """One block in a stage pattern."""

    mixer: str = "attn"  # attn | cross_attn | enc_attn | mamba | mlstm | slstm
    ffn: str = "dense"  # dense | moe | none

    @property
    def tag(self) -> str:
        return f"{self.mixer}.{self.ffn}"


@dataclasses.dataclass(frozen=True)
class Stage:
    pattern: Tuple[BlockSpec, ...]
    repeats: int

    @property
    def n_layers(self) -> int:
        return len(self.pattern) * self.repeats


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | vlm | audio | vit | encoder
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    stages: Tuple[Stage, ...]
    head_dim: int = 0  # 0 -> d_model // n_heads

    # attention
    attn_type: str = "gqa"  # gqa | mla
    causal: bool = True
    qk_norm: bool = False
    rope_theta: float = 10000.0
    attn_logit_softcap: float = 0.0

    # MLA (deepseek-v3)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01

    # Mamba (jamba)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0  # 0 -> ceil(d_model/16)

    # xLSTM
    xlstm_proj_factor: float = 2.0

    # encoder-decoder (whisper)
    n_encoder_layers: int = 0
    encoder_seq: int = 0  # e.g. 1500 mel frames after conv frontend (stub)

    # VLM cross attention
    n_image_tokens: int = 0
    cross_attn_period: int = 0  # informational; pattern encodes positions
    vision_dim: int = 0  # stub frontend feature dim (0 -> d_model); NOT coalesced

    # ViT
    image_size: int = 224
    patch_size: int = 16
    n_classes: int = 1000

    # embeddings / head
    tie_embeddings: bool = True
    vocab_pad_to: int = 128
    mtp_depth: int = 0  # deepseek multi-token prediction heads
    mtp_loss_weight: float = 0.3

    # numerics
    act: str = "silu"  # silu | gelu
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-5
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    use_bias: bool = False

    # performance knobs (hillclimbing levers)
    ssm_chunk: int = 128  # recurrent-scan remat chunk (memory / (S/chunk))
    attn_seq_shard: bool = False  # shard attn activations along seq (context
    # parallelism) when the head count does not divide the model axis
    # plain | blockwise | pallas | pairs.  Past 128 tokens, blockwise and
    # pallas let the shapes decide: on a TPU the Pallas flash kernels where
    # they can express the call, else the XLA flash recipe; off TPU
    # "pallas" prefers the interpreted kernels (layers/attention.py)
    attn_impl: str = "blockwise"
    attn_block_k: int = 512
    kernel_backend: str = ""  # "" = auto; else pallas | pallas-interpret | xla
    # (per-op resolution lives in repro.kernels.dispatch; REPRO_KERNEL_BACKEND
    # env overrides the auto default, this field overrides both)
    remat: str = "full"  # none | full | dots
    scan_layers: bool = True
    seq_shard_cache: bool = True  # shard decode KV/latent cache seq over "model"
    coalesce_experts: bool = False  # beyond-paper: pair-merge experts too

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def n_layers(self) -> int:
        return sum(s.n_layers for s in self.stages)

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return ((self.vocab_size + p - 1) // p) * p

    @property
    def resolved_dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.d_model // 16)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


def uniform_stages(n_layers: int, block: BlockSpec) -> Tuple[Stage, ...]:
    return (Stage(pattern=(block,), repeats=n_layers),)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str  # train_4k | prefill_32k | decode_32k | long_500k
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 300
    warmup_steps: int = 20
    peak_lr: float = 1e-3
    end_lr_frac: float = 0.1
    schedule: str = "cosine"  # cosine | linear | constant
    weight_decay: float = 0.01
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    grad_accum: int = 1
    opt_dtype: Any = jnp.float32  # adam moment dtype (bf16 for giant dry-runs)
    seed: int = 0
    batch_size: int = 8
    seq_len: int = 64
    log_every: int = 10
    grad_compression: str = "none"  # none | int8_ef (shard_map DP all-reduce)
    z_loss: float = 0.0
    pregather_params: bool = False  # per-step FSDP weight gather (vs per-layer
    # per-microbatch); opt-in where total_bf16/model_shard fits HBM


@dataclasses.dataclass(frozen=True)
class MultiLevelConfig:
    """Paper Algorithm 1 hyper-parameters (fractions of total step budget)."""

    n_levels: int = 2
    alpha: float = 0.25  # interpolation ratio (paper: 0.25 GPT/DeiT, 0.5 BERT)
    e_a_frac: float = 0.033  # E_a: init steps per level before coalescing (10K/300K)
    e_small_frac: float = 0.5  # E_small: small-model steps (one half of full cycle)
    width_variant: str = "stack"  # stack | adj  (Appendix E)
    depth_variant: str = "adj"  # adj | stack   (Appendix E)
    reset_opt: bool = True  # paper re-inits optimizer at transitions
    coalesce_experts: bool = False


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n
