"""Config dataclasses for models, SWM compression, parallelism, and shapes.

Every assigned architecture gets a ``src/repro/configs/<id>.py`` exporting
``CONFIG`` (full size, exact paper/HF numbers) and ``SMOKE`` (reduced same-
family config for CPU tests). ``repro.configs.registry`` resolves ``--arch``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# SWM (the paper's technique)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SWMConfig:
    """Block-circulant compression settings (paper §3/§4).

    block_size: k. 0 or 1 disables (dense baseline).
    impl: 'paper' | 'freq' | 'dft' | 'pallas'  (see core.circulant)
    targets: which projection families are compressed. Components that are
      not plain weight GEMMs (routing, scans, embeddings) are never touched
      — see DESIGN.md §Arch-applicability.
    """

    block_size: int = 0
    impl: str = "freq"
    karatsuba: bool = False
    targets: Tuple[str, ...] = ("attn", "ffn", "expert")

    @property
    def enabled(self) -> bool:
        return self.block_size > 1

    def applies_to(self, family: str) -> bool:
        return self.enabled and family in self.targets


# ---------------------------------------------------------------------------
# Layer pattern descriptors
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One decoder layer's composition within a scan group.

    mixer: 'attn' | 'attn_local' | 'mla' (latent attention) | 'mamba' |
           'rwkv'
    ffn:   'dense' | 'moe' | 'dense+moe' (a dense FFN beside the experts:
           arctic's parallel residual, deepseek's shared experts) | 'none'
    """

    mixer: str = "attn"
    ffn: str = "dense"


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    """``layers`` repeated ``repeat`` times via lax.scan (params stacked)."""

    layers: Tuple[LayerSpec, ...]
    repeat: int


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

    Keys and values of every head are expanded from one cached latent of
    ``kv_lora_rank`` plus one ``qk_rope_head_dim`` rotary key shared by all
    heads; a query head is ``qk_nope_head_dim + qk_rope_head_dim`` wide and
    a value head ``v_head_dim``. Queries are projected directly (no
    ``q_lora_rank``)."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Width of one cached position: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling as ``DeepseekV2YarnRotaryEmbedding`` defines it
    (``rope_scaling`` of type ``yarn``)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "lm"          # lm | encdec | vlm
    # dims
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 2
    n_kv_heads: int = 2
    head_dim: int = 64
    d_ff: int = 256
    vocab: int = 256
    # attention
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    rope_theta_local: float = 10_000.0
    sliding_window: int = 0             # >0: width of local attention
    local_global_pattern: int = 0       # gemma3: N local per 1 global
    logit_softcap: float = 0.0
    flash_q_chunk: int = 512
    flash_kv_chunk: int = 1024
    mla: Optional[MLAConfig] = None     # latent attention on every layer
    yarn: Optional[YarnConfig] = None   # rotary scaling (mla layers)
    # ffn / moe
    n_experts: int = 0
    n_experts_per_token: int = 0
    d_ff_expert: int = 0
    moe_every: int = 1                  # jamba: MoE on every Nth layer
    dense_residual_ffn: bool = False    # arctic: dense FFN in parallel w/ MoE
    d_ff_shared: int = 0                # that FFN's width (0: d_ff)
    first_dense_layers: int = 0         # deepseek: leading dense-FFN layers
    moe_norm_topk: bool = True          # renormalise the top-k gates
    moe_routed_scale: float = 1.0       # factor on un-normalised gates
    capacity_factor: float = 1.25
    # mamba (hybrid)
    attn_every: int = 0                 # jamba: attention every Nth layer
    attn_offset: int = 0
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0              # 0 -> d_model // 16
    # rwkv
    rwkv_head_dim: int = 64
    rwkv_decay_lora: int = 64
    rwkv_mix_lora: int = 32
    # encdec / vlm frontends (stubs provide embeddings directly)
    n_enc_layers: int = 0
    enc_seq: int = 0                    # encoder frames for encdec stubs
    n_img_tokens: int = 0               # vlm prefix length
    tie_embeddings: bool = True
    # numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    norm_dtype: str = "float32"
    # compression
    swm: SWMConfig = dataclasses.field(default_factory=SWMConfig)
    # distribution
    fsdp: bool = False                  # shard params over data axis too
    low_tp: bool = False                # replicate SWM tables (no head/mlp TP)
    remat: str = "block"                # none | block | full
    scan_layers: bool = True
    optimizer: str = "adamw"            # adamw | adafactor
    # architecture pattern override (derived if None)
    groups: Optional[Tuple[LayerGroup, ...]] = None

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(1, self.n_heads))

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def layer_groups(self) -> Tuple[LayerGroup, ...]:
        """Derive the scan-group structure from the pattern fields."""
        if self.groups is not None:
            return self.groups
        specs = []
        for i in range(self.n_layers):
            if self.mla is not None:
                mixer = "mla"
            elif self.attn_every > 0:
                mixer = "attn" if i % self.attn_every == self.attn_offset else "mamba"
            elif self.local_global_pattern > 0:
                period = self.local_global_pattern + 1
                mixer = "attn" if (i % period) == self.local_global_pattern else "attn_local"
            elif self.sliding_window > 0:
                mixer = "attn_local"        # no pattern -> all-local
            else:
                mixer = "attn"
            if (self.is_moe and i >= self.first_dense_layers
                    and i % self.moe_every == self.moe_every - 1):
                ffn = "dense+moe" if self.dense_residual_ffn else "moe"
            else:
                ffn = "dense"
            specs.append(LayerSpec(mixer=mixer, ffn=ffn))
        # leading dense layers are their own group, so the rest stays one
        # scan (one pattern over all layers would find no period)
        k = self.first_dense_layers
        return _group_layers(tuple(specs[:k])) + _group_layers(
            tuple(specs[k:]))


def _group_layers(specs: Tuple[LayerSpec, ...]) -> Tuple[LayerGroup, ...]:
    """Factor the per-layer spec list into repeated groups for lax.scan.

    Finds the smallest period P such that the sequence is (prefix of) a
    repetition of its first P entries; trailing partial periods become their
    own group(s).
    """
    n = len(specs)
    if not n:
        return ()
    for period in range(1, n + 1):
        pattern = specs[:period]
        if all(specs[i] == pattern[i % period] for i in range(n)):
            full, rem = divmod(n, period)
            groups = []
            if full:
                groups.append(LayerGroup(layers=pattern, repeat=full))
            if rem:
                groups.append(LayerGroup(layers=specs[full * period :], repeat=1))
            return tuple(groups)
    return (LayerGroup(layers=specs, repeat=1),)


# ---------------------------------------------------------------------------
# Input shapes (the assignment's 4 shapes)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str               # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    seed: int = 0
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1_000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    moment_dtype: str = "float32"
    z_loss: float = 1e-4
    moe_aux_loss: float = 1e-2
    microbatch: int = 0                 # 0 = no gradient accumulation
    grad_compression: str = "none"      # none | int8_ef
    checkpoint_every: int = 200
    checkpoint_dir: str = "/tmp/repro_ckpt"
    # quantization-aware training: fake-quantize params through the clipped
    # STE every forward (0 = off). frac_bits -1 derives bits-4, matching the
    # paper's fixed-point split; biases/norm scales are exempt
    # (quant.default_exempt).
    qat_bits: int = 0
    qat_frac_bits: int = -1
