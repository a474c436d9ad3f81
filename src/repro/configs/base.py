"""Model/architecture configuration schema + assigned input shapes.

Every assigned architecture is expressed as a :class:`ModelConfig`; the four
LM shapes (train_4k / prefill_32k / decode_32k / long_500k) are global.
``input_specs`` produces ShapeDtypeStruct stand-ins (no allocation) for the
dry-run; smoke tests instantiate ``reduced()`` configs on CPU.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

Family = Literal["dense", "moe", "ssm", "hybrid", "audio", "vlm"]
RopeKind = Literal["none", "standard", "rope2d", "mrope"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int  # per-expert FFN width
    n_shared_experts: int = 0
    # the experts this chip holds, ``[held_first, held_first + held_count)``
    # (0 = all): a held layer (``moe.held_moe``) routes over every expert,
    # is dropless, and computes its own experts' part plus the shared
    # experts
    held_first: int = 0
    held_count: int = 0

    @property
    def n_held(self) -> int:
        return self.held_count or self.n_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): keys and values come from
    one compressed latent of ``kv_rank`` per token, plus a ``qk_rope_dim``
    RoPE key shared by all heads; the cache holds both (``cache_width``)."""

    kv_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int

    @property
    def cache_width(self) -> int:
        return self.kv_rank + self.qk_rope_dim

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN RoPE scaling (arXiv:2309.00071), as DeepSeek-V2 configures it."""

    factor: float
    original_max_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 => d_model // n_heads
    rope: RopeKind = "standard"
    qk_norm: bool = False
    qkv_bias: bool = False
    moe: MoEConfig | None = None
    # attention-free / hybrid structure
    attn_free: bool = False  # rwkv6: no attention at all
    layer_pattern: tuple[str, ...] = ()  # e.g. ("rec", "rec", "attn") cycle
    local_window: int = 0  # sliding-window size for local attention layers
    rwkv_head_dim: int = 64
    # frontend stubs ([audio]/[vlm]): inputs arrive as precomputed embeddings
    frontend: Literal["none", "audio", "vision"] = "none"
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # §Perf: flash-style blocked attention (no S^2 materialization)
    blocked_attention: bool = False
    # §Perf: sectored decode shares page selection across kv heads
    sector_share_heads: bool = False
    # latent attention (replaces the q/k/v projections; n_kv_heads is 1,
    # the one latent group the cache and the sector table hold)
    mla: MLAConfig | None = None
    yarn: YarnConfig | None = None
    # leading layers with a dense SwiGLU of width d_ff before the MoE ones
    first_dense_layers: int = 0

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        """Per-layer block kinds, length n_layers."""
        if self.attn_free:
            return ("rwkv",) * self.n_layers
        if self.layer_pattern:
            reps = (self.n_layers + len(self.layer_pattern) - 1) // len(self.layer_pattern)
            return (self.layer_pattern * reps)[: self.n_layers]
        return ("attn",) * self.n_layers

    @property
    def uniform_layers(self) -> bool:
        return len(set(self.layer_kinds)) == 1 and self.layer_kinds[0] in ("attn",)

    def param_count(self) -> int:
        """Approximate parameter count (for 6ND model-FLOPs accounting)."""
        emb = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return emb + sum(self._layer_params(k, i < self.first_dense_layers)
                         for i, k in enumerate(self.layer_kinds))

    def _layer_params(self, kind: str, dense: bool = False) -> int:
        d = self.d_model
        hd = self.head_dim_
        n = 0
        if kind == "attn":
            if self.mla:
                m, h = self.mla, self.n_heads
                n += (d * h * m.qk_dim + d * m.cache_width + m.kv_rank
                      + m.kv_rank * h * (m.qk_nope_dim + m.v_head_dim)
                      + h * m.v_head_dim * d)
            else:
                n += (d * hd * (self.n_heads + 2 * self.n_kv_heads)
                      + self.n_heads * hd * d)
            if self.moe and not dense:
                e = self.moe
                n += e.n_experts * 3 * d * e.d_expert + d * e.n_experts
                n += e.n_shared_experts * 3 * d * e.d_expert
            else:
                n += 3 * d * self.d_ff
        elif kind == "rwkv":
            n += 4 * d * d + 2 * d * self.d_ff
        elif kind == "rec":
            n += 2 * d * d + 3 * d * self.d_ff
        return n

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: top_k of n_experts)."""
        if not self.moe:
            return self.param_count()
        d, e = self.d_model, self.moe
        n_moe = self.n_layers - self.first_dense_layers
        dense = self.param_count() - n_moe * e.n_experts * 3 * d * e.d_expert
        active = n_moe * (e.top_k + e.n_shared_experts) * 3 * d * e.d_expert
        return dense + active

    def reduced(self, **overrides) -> "ModelConfig":
        """Small same-family config for CPU smoke tests."""
        kw = dict(
            name=self.name + "-smoke",
            n_layers=min(self.n_layers, 2 if not self.layer_pattern else len(self.layer_pattern) or 2),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) or 1,
            d_ff=256,
            vocab=256,
            head_dim=32,
            local_window=min(self.local_window, 32) if self.local_window else 0,
        )
        if self.layer_pattern:
            kw["n_layers"] = len(self.layer_pattern)
        if self.moe:
            kw["moe"] = dataclasses.replace(self.moe, n_experts=4, top_k=2,
                                            d_expert=64, held_first=0,
                                            held_count=0)
        if self.mla:
            kw.update(n_kv_heads=1, head_dim=24,
                      mla=MLAConfig(kv_rank=32, qk_nope_dim=16,
                                    qk_rope_dim=8, v_head_dim=16))
        if self.first_dense_layers:
            kw["n_layers"] = self.first_dense_layers + 1
        kw.update(overrides)
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def token_specs(shape: ShapeConfig):
    """ShapeDtypeStructs for a training/prefill batch."""
    B, S = shape.global_batch, shape.seq_len
    return dict(
        tokens=jax.ShapeDtypeStruct((B, S), jnp.int32),
        labels=jax.ShapeDtypeStruct((B, S), jnp.int32),
    )
