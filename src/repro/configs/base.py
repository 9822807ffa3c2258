"""Architecture configuration schema + registry + input specs.

Every assigned architecture gets one module in this package defining a
``CONFIG`` (full published size) and a ``SMOKE`` (reduced same-family config
for CPU tests).  The dry-run instantiates FULL configs only through
``jax.eval_shape`` / ShapeDtypeStruct — never allocated.

Shape suite (assignment): train_4k / prefill_32k / decode_32k / long_500k,
with per-arch skips (encoder-only -> no decode; full-attention -> no 500k).
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model // n_heads
    # attention flavour
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_fraction: float = 1.0  # chatglm3 applies RoPE to half the head dim
    rope_theta: float = 10_000.0
    causal: bool = True
    # mlp flavour
    mlp_act: str = "swiglu"  # swiglu | geglu | gelu
    # MoE
    n_experts: int = 0
    n_experts_padded: int = 0  # padded for EP divisibility (0 = n_experts)
    top_k: int = 0
    d_ff_expert: int = 0
    shared_expert_ff: int = 0
    moe_period: int = 1  # layer l uses MoE iff n_experts>0 and l % moe_period == moe_offset
    moe_offset: int = 0
    # hybrid / SSM
    attn_period: int = 0  # 0 = attention everywhere; k>0 -> attention iff l%k==attn_offset
    attn_offset: int = 0
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # structure
    encoder_only: bool = False
    tie_embeddings: bool = False
    frontend: str = "none"  # none | audio_frames | vision_patches
    frontend_dim: int = 0  # raw patch/frame embedding width (projected to d_model)
    frontend_len: int = 0  # number of prefix embeddings
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # muP-style scalings (Granite); the defaults leave the model unscaled
    embedding_multiplier: float = 1.0  # embedding lookup times this
    attention_multiplier: float = 0.0  # softmax scale; 0 -> 1/sqrt(head dim)
    residual_multiplier: float = 1.0  # each block's branch times this
    logits_scaling: float = 1.0  # logits divided by this
    # padding applied for TP/EP divisibility (documented in DESIGN.md §4.1)
    n_heads_padded: int = 0
    n_kv_heads_padded: int = 0
    vocab_padded: int = 0
    ssm_heads_padded: int = 0

    # -- derived -------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def attn_scale(self) -> float:
        return self.attention_multiplier or self.hd ** -0.5

    @property
    def heads(self) -> int:
        return self.n_heads_padded or self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads_padded or self.n_kv_heads

    @property
    def experts(self) -> int:
        return self.n_experts_padded or self.n_experts

    @property
    def vocab_p(self) -> int:
        return self.vocab_padded or self.vocab

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def ssm_heads_p(self) -> int:
        return self.ssm_heads_padded or self.ssm_heads

    @property
    def d_inner_p(self) -> int:
        return self.ssm_heads_p * self.ssm_head_dim

    def is_attn_layer(self, layer: int) -> bool:
        if self.ssm_state == 0:
            return True
        if self.attn_period == 0:
            return False  # pure SSM
        return layer % self.attn_period == self.attn_offset

    def is_moe_layer(self, layer: int) -> bool:
        return self.n_experts > 0 and layer % self.moe_period == self.moe_offset

    @property
    def block_period(self) -> int:
        """Smallest repeating layer pattern (scan super-block size)."""
        import math

        p = 1
        if self.n_experts > 0:
            p = math.lcm(p, self.moe_period)
        if self.ssm_state > 0 and self.attn_period > 0:
            p = math.lcm(p, self.attn_period)
        return p

    def param_count(self) -> int:
        """Approximate parameter count (true config, ignoring TP padding)."""
        hd = self.hd
        n = self.vocab * self.d_model  # embed
        if not self.tie_embeddings:
            n += self.vocab * self.d_model
        for li in range(self.n_layers):
            if self.is_attn_layer(li):
                n += self.d_model * (self.n_heads * hd) + self.d_model * (
                    2 * self.n_kv_heads * hd
                )
                n += self.n_heads * hd * self.d_model
            elif self.ssm_state > 0:
                di = self.d_inner
                n += self.d_model * (2 * di + 2 * self.ssm_state + self.ssm_heads)
                n += di * self.d_model + self.ssm_conv * (di + 2 * self.ssm_state)
            if self.is_moe_layer(li):
                n += self.d_model * self.n_experts  # router
                n += self.n_experts * 3 * self.d_model * self.d_ff_expert
                if self.shared_expert_ff:
                    n += 3 * self.d_model * self.shared_expert_ff
            elif self.d_ff > 0:
                mult = 3 if self.mlp_act in ("swiglu", "geglu") else 2
                n += mult * self.d_model * self.d_ff
            n += 2 * self.d_model  # norms
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE top-k instead of all experts)."""
        if self.n_experts == 0:
            return self.param_count()
        full = self.param_count()
        moe_layers = sum(1 for li in range(self.n_layers) if self.is_moe_layer(li))
        all_exp = moe_layers * self.n_experts * 3 * self.d_model * self.d_ff_expert
        act_exp = moe_layers * self.top_k * 3 * self.d_model * self.d_ff_expert
        return full - all_exp + act_exp


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}

ARCH_IDS = [
    "qwen1_5_4b",
    "chatglm3_6b",
    "gemma_2b",
    "qwen3_4b",
    "jamba_1_5_large",
    "hubert_xlarge",
    "mamba2_130m",
    "granite_moe_3b",
    "moonshot_v1_16b",
    "internvl2_26b",
]

# extra configs used by the verifier benchmarks (the paper's own tables)
EXTRA_IDS = ["llama3_8b", "llama3_70b", "llama3_405b", "mixtral_8x7b", "mixtral_8x22b"]


def get_config(arch: str, smoke: bool = False) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.SMOKE if smoke else mod.CONFIG


def skip_reason(cfg: ArchConfig, shape: str) -> Optional[str]:
    """Assignment skip rules (recorded in DESIGN.md / EXPERIMENTS.md)."""
    spec = SHAPES[shape]
    if cfg.encoder_only and spec.kind == "decode":
        return "encoder-only architecture has no decode step"
    if shape == "long_500k":
        sub_quadratic = cfg.ssm_state > 0  # pure SSM or hybrid
        if not sub_quadratic:
            return "pure full-attention arch: 500k decode restricted to SSM/hybrid per assignment"
    return None


def input_specs(cfg: ArchConfig, shape: str, dp_shards: int = 1):
    """ShapeDtypeStruct stand-ins for every model input of a given shape cell
    (weak-type-correct, shardable, no device allocation).

    train  -> {tokens, labels}            (B, S) int32
    prefill-> {tokens}                    (B, S) int32
    decode -> {token, cache, position}    one new token + KV cache of S
    Modality frontends are stubs: precomputed frame/patch embeddings.
    """
    spec = SHAPES[shape]
    B, S = spec.global_batch, spec.seq_len
    i32 = jnp.int32
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    out = {}
    if cfg.frontend == "vision_patches":
        txt = S - cfg.frontend_len
        out["vision_embeds"] = jax.ShapeDtypeStruct((B, cfg.frontend_len, cfg.frontend_dim), dt)
        tok_len = txt
    elif cfg.frontend == "audio_frames":
        out["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
        tok_len = 0
    else:
        tok_len = S
    if spec.kind == "train":
        if tok_len:
            out["tokens"] = jax.ShapeDtypeStruct((B, tok_len), i32)
        out["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    elif spec.kind == "prefill":
        if tok_len:
            out["tokens"] = jax.ShapeDtypeStruct((B, tok_len), i32)
    else:  # decode
        out["token"] = jax.ShapeDtypeStruct((B,), i32)
        out["position"] = jax.ShapeDtypeStruct((), i32)
        # cache specs are provided by the model (per-layer kinds differ)
    return out
