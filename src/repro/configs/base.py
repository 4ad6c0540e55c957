"""Architecture + input-shape configuration schema.

One ``ArchConfig`` per assigned architecture (exact numbers from the
assignment table), plus the paper's own LeNet configs.  ``reduced()``
derives the smoke-test variant (same family, tiny dims).  ``input_specs``
produces jax.ShapeDtypeStruct stand-ins for the dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    # attention details
    head_dim: int = 0              # 0 -> d_model // n_heads
    window: Optional[int] = None   # sliding-window attention (mixtral)
    qkv_bias: bool = False         # qwen2.5
    rope_theta: float = 10_000.0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # SSM (mamba2 / zamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # hybrid (zamba2): one *shared* attention block applied every N layers
    attn_every: int = 0
    # enc-dec (seamless)
    encoder_layers: int = 0
    # vlm (llama-3.2-vision): cross-attention every N layers
    cross_attn_every: int = 0
    n_vision_tokens: int = 0
    # norm / misc
    norm: str = "rmsnorm"
    norm_eps: float = 1e-6         # every RMSNorm: block, gated, final
    residual_f32: bool = False     # ssm: carry the residual stream in f32
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    sub_quadratic: bool = False    # eligible for long_500k
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def d_inner(self) -> int:       # SSM inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def conv_dim(self) -> int:      # channels of the causal conv: x, B, C
        return self.d_inner + 2 * self.ssm_state

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    def dtype_(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def supports(self, shape: str) -> bool:
        """Which assigned shapes this arch runs (skips are per-assignment)."""
        if shape == "long_500k":
            return self.sub_quadratic
        return True

    def reduced(self) -> "ArchConfig":
        """Tiny same-family variant for CPU smoke tests."""
        small = dict(
            # f32: XLA:CPU cannot execute bf16 batched dots (full configs
            # stay bf16 — they are only compiled, via the dry-run)
            dtype="float32",
            n_layers=max(2, min(4, self.n_layers)),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(4, max(1, self.n_kv_heads * 4 // self.n_heads))
            if self.n_heads
            else 0,
            d_ff=128 if self.d_ff else 0,
            vocab_size=128,
            head_dim=16,
        )
        if self.n_experts:
            small.update(n_experts=4, top_k=min(2, self.top_k))
        if self.ssm_state:
            small.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=16)
        if self.attn_every:
            small.update(attn_every=2, n_layers=4)
        if self.encoder_layers:
            small.update(encoder_layers=2)
        if self.cross_attn_every:
            small.update(cross_attn_every=2, n_layers=4, n_vision_tokens=8)
        if self.window:
            small["window"] = 32
        return dataclasses.replace(self, name=self.name + "-smoke", **small)

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Analytic parameter count (used for roofline MODEL_FLOPS)."""
        d, v = self.d_model, self.vocab_size
        hd = self.head_dim_
        emb = v * d * (1 if self.tie_embeddings else 2)
        per_attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
            + (self.n_heads * hd) * d
        if self.family in ("ssm", "hybrid"):
            # mamba2 block: in_proj (d -> 2*d_inner + 2*G*N + H), conv
            # weight and bias over x|B|C, A_log/D/dt_bias, out_proj, the
            # block norm and the gated norm
            di, n, h, cd = self.d_inner, self.ssm_state, self.ssm_heads, \
                self.conv_dim
            per_m = d * (2 * di + 2 * n + h) + cd * (self.ssm_conv + 1) \
                + 3 * h + di * d + d + di
            total = emb + d + self.n_layers * per_m     # d: the final norm
            if self.family == "hybrid":
                total += per_attn + 3 * d * self.d_ff + 2 * d
            return total
        per_mlp = 3 * d * self.d_ff
        if self.n_experts:
            per_mlp = self.n_experts * 3 * d * self.d_ff + d * self.n_experts
        per_layer = per_attn + per_mlp + 2 * d
        total = emb + self.n_layers * per_layer
        if self.cross_attn_every:
            n_cross = self.n_layers // self.cross_attn_every
            total += n_cross * (per_attn + 2 * d)
        if self.encoder_layers:
            # encoder self-attn+mlp, decoder adds cross-attn
            total += self.encoder_layers * (per_attn + per_mlp + 2 * d)
            total += self.n_layers * (per_attn + d)
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed experts)."""
        if not self.n_experts:
            return self.param_count()
        d = self.d_model
        dense_like = self.param_count() - self.n_layers * (
            self.n_experts * 3 * d * self.d_ff
        )
        return dense_like + self.n_layers * self.top_k * 3 * d * self.d_ff


def input_specs(
    cfg: ArchConfig, shape: ShapeSpec, *, batch_override: Optional[int] = None
) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b = batch_override or shape.global_batch
    s = shape.seq_len
    i32 = jnp.int32
    dt = cfg.dtype_()
    if shape.kind == "train":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s + 1), i32)}
        if cfg.family == "vlm":
            specs["vision"] = jax.ShapeDtypeStruct(
                (b, cfg.n_vision_tokens, cfg.d_model), dt
            )
        if cfg.family == "encdec":
            specs["frames"] = jax.ShapeDtypeStruct((b, s // 4, cfg.d_model), dt)
            specs["tokens"] = jax.ShapeDtypeStruct((b, s + 1), i32)
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if cfg.family == "vlm":
            specs["vision"] = jax.ShapeDtypeStruct(
                (b, cfg.n_vision_tokens, cfg.d_model), dt
            )
        if cfg.family == "encdec":
            specs["frames"] = jax.ShapeDtypeStruct((b, s // 4, cfg.d_model), dt)
            specs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
        return specs
    # decode: one new token against a cache of length seq_len
    specs = {
        "token": jax.ShapeDtypeStruct((b,), i32),
        "pos": jax.ShapeDtypeStruct((), i32),
    }
    return specs
