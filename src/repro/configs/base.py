"""Model/shape configuration system.

Every architecture is a pure function of a frozen :class:`ModelConfig`.
Input shapes are frozen :class:`ShapeConfig` records; the cross product of
(arch x shape) defines the benchmark/dry-run cells.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field, replace
from typing import Optional

# ---------------------------------------------------------------------------
# Shape configs (assigned input-shape set; identical for all LM-family archs)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    """One benchmark cell's input shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    @property
    def tokens_per_step(self) -> int:
        if self.kind == "decode":
            return self.global_batch  # one new token per sequence
        return self.seq_len * self.global_batch


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

SHAPES: dict[str, ShapeConfig] = {
    s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
}


def vocab_pad(vocab: int, multiple: int = 256) -> int:
    """Megatron-style vocab padding (make_vocab_size_divisible_by)."""
    return int(math.ceil(vocab / multiple) * multiple)


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description. All models are pure functions of this.

    family:
      dense   — decoder-only transformer (GQA)
      moe     — decoder-only with MoE FFN layers
      ssm     — attention-free Mamba2 (SSD)
      hybrid  — Mamba2 + periodic attention (+ optional MoE) (Jamba)
      encdec  — encoder-decoder transformer (Whisper backbone)
      vlm     — decoder-only with prepended patch embeddings (LLaVA backbone)
    """

    name: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    # attention
    d_head: int = 0                 # 0 -> d_model // n_heads
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    mlp_bias: bool = False
    attn_window: Optional[int] = None   # chunked/local attention (tokens)
    positions: str = "rope"             # "rope" | "learned" (absolute
    #                                     table) | "none" (NoPE: no table)
    max_position: int = 1 << 20         # for learned positions only
    logits_softcap: float = 0.0

    # scalar multipliers (Granite): embeddings x embed_mult, each residual
    # branch x resid_mult, attention scores x attn_mult (0 -> 1/sqrt(d_head)),
    # logits / logits_div. The defaults leave the programs as they were.
    embed_mult: float = 1.0
    resid_mult: float = 1.0
    attn_mult: float = 0.0
    logits_div: float = 1.0

    # norms / activations
    norm: str = "rmsnorm"           # "rmsnorm" | "layernorm"
    act: str = "swiglu"             # "swiglu" | "gelu"
    tie_embeddings: bool = False

    # MoE
    n_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0
    moe_layer_step: int = 1         # every k-th layer is MoE (1 = all)
    shared_d_ff: int = 0            # shared expert's width (0 = none); it
    #                                 runs in parallel with the routed ones
    n_experts_held: int = 0         # experts this chip holds: [0, n); 0 = all
    moe_dispatch: str = "capacity"  # "capacity": per-expert buffers of
    #   capacity_factor x the fair share (overflow dropped; the experts
    #   shard over the mesh); "dropless": a grouped matmul over the held
    #   experts (moe.moe_share_forward)
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01

    # SSM (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 256
    ssm_conv: int = 4
    attn_layer_period: int = 0      # hybrid: one attn layer per period
    attn_layer_offset: int = 0      # index of the attn layer inside period

    # encoder-decoder (Whisper backbone)
    n_enc_layers: int = 0
    enc_seq: int = 0                # precomputed frame embeddings length

    # VLM
    n_patches: int = 0              # precomputed patch embeddings length

    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    # bookkeeping
    source: str = ""
    long_context_ok: bool = False   # may run long_500k (sub-quadratic path)

    # ---------------------------------------------------------------
    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // max(self.n_heads, 1))
        assert self.positions in ("rope", "learned", "none"), self.positions
        assert self.moe_dispatch in ("capacity", "dropless"), self.moe_dispatch
        assert self.n_experts_held == 0 or self.moe_dispatch == "dropless", (
            "only the dropless expert layer computes a held share")

    # Derived quantities -------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        return vocab_pad(self.vocab)

    @property
    def group_size(self) -> int:
        """GQA group size (query heads per KV head)."""
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def d_inner(self) -> int:
        """Mamba inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim if self.ssm_headdim else 0

    @property
    def experts_held(self) -> int:
        """Experts whose weights this chip holds (ids ``[0, held)``)."""
        return self.n_experts_held or self.n_experts

    def is_moe_layer(self, i: int) -> bool:
        if self.n_experts == 0:
            return False
        # layers i with (i % step == step-1) are MoE (e.g. step=2 -> 1,3,5..)
        return (i % self.moe_layer_step) == (self.moe_layer_step - 1)

    def is_attn_layer(self, i: int) -> bool:
        """For hybrid archs: whether layer i is attention (else Mamba)."""
        if self.family != "hybrid":
            return self.family != "ssm"
        return (i % self.attn_layer_period) == self.attn_layer_offset

    # Parameter counting (analytic; used by roofline + metrics) ----------
    def param_count(self) -> int:
        return _param_count(self, active_only=False)

    def active_param_count(self) -> int:
        return _param_count(self, active_only=True)

    def reduced(self, **overrides) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        small: dict = dict(
            n_layers=max(2, self.moe_layer_step * max(1, self.attn_layer_period or 1)),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) or 2,
            d_ff=128,
            vocab=512,
            d_head=16,
        )
        if self.n_experts:
            small.update(n_experts=min(self.n_experts, 4), expert_d_ff=64,
                         top_k=min(self.top_k, 2),
                         n_experts_held=min(self.n_experts_held, 2),
                         shared_d_ff=min(self.shared_d_ff, 64))
        if self.ssm_state:
            small.update(ssm_state=16, ssm_headdim=16, ssm_chunk=32)
        if self.attn_layer_period:
            small.update(attn_layer_period=min(self.attn_layer_period, 4),
                         attn_layer_offset=min(self.attn_layer_offset, 3),
                         n_layers=2 * min(self.attn_layer_period, 4))
        if self.n_enc_layers:
            small.update(n_enc_layers=2, enc_seq=32)
        if self.n_patches:
            small.update(n_patches=16)
        if self.family == "ssm":
            small.update(n_heads=0, n_kv_heads=0, d_ff=0, d_head=0)
        small["name"] = self.name + "-reduced"
        small.update(overrides)
        return replace(self, **small)


def _attn_params(c: ModelConfig) -> int:
    qo = 2 * c.d_model * c.n_heads * c.d_head
    kv = 2 * c.d_model * c.n_kv_heads * c.d_head
    bias = (c.n_heads + 2 * c.n_kv_heads) * c.d_head if c.qkv_bias else 0
    return qo + kv + bias


def _mlp_params(c: ModelConfig, d_ff: int) -> int:
    n_mats = 3 if c.act == "swiglu" else 2
    return n_mats * c.d_model * d_ff + (c.mlp_bias and (n_mats - 1) * d_ff + c.d_model or 0)


def _mamba_params(c: ModelConfig) -> int:
    di, ns, nh = c.d_inner, c.ssm_state, c.ssm_nheads
    in_proj = c.d_model * (2 * di + 2 * ns + nh)  # z, x, B, C, dt
    conv = (di + 2 * ns) * (c.ssm_conv + 1)       # taps and bias
    out = di * c.d_model
    extras = 3 * nh + di  # dt_bias, A_log, D, norm
    return in_proj + conv + out + extras


def _param_count(c: ModelConfig, active_only: bool) -> int:
    total = c.padded_vocab * c.d_model  # embedding
    if not c.tie_embeddings:
        total += c.padded_vocab * c.d_model  # lm head
    if c.n_patches:
        total += 0  # patch frontend is a stub (precomputed embeddings)
    per_norm = c.d_model * (2 if c.norm == "layernorm" else 1)

    def layer_params(i: int, cross: bool = False) -> int:
        p = 0
        if c.is_attn_layer(i):
            p += _attn_params(c) + per_norm
            if cross:
                p += _attn_params(c) + per_norm
        else:
            p += _mamba_params(c) + per_norm
        if c.family in ("ssm",):
            return p
        if c.family == "hybrid" and not c.is_attn_layer(i):
            # mamba layer still followed by FFN in Jamba
            pass
        if c.is_moe_layer(i):
            eff = c.expert_d_ff or c.d_ff
            n_used = c.top_k if active_only else c.experts_held
            p += n_used * _mlp_params(c, eff) + per_norm
            if c.shared_d_ff:
                p += _mlp_params(c, c.shared_d_ff)
            p += c.d_model * c.n_experts  # router
        elif c.d_ff:
            p += _mlp_params(c, c.d_ff) + per_norm
        return p

    for i in range(c.n_layers):
        total += layer_params(i, cross=c.family == "encdec")
    for i in range(c.n_enc_layers):
        total += _attn_params(c) + _mlp_params(c, c.d_ff) + 2 * per_norm
    total += per_norm  # final norm
    return total
