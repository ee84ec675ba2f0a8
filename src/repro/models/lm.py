"""Top-level language models: decoder-only (dense/moe/ssm/hybrid/vlm) and
encoder-decoder (whisper backbone). Pure functions of (config, params).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import blocks
from repro.models.common import (
    apply_norm, dtype_of, embed_tokens, embedding_init, norm_init, unembed,
)

Params = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init(key, c: ModelConfig) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "embed": embedding_init(k1, c),
        "layers": blocks.stack_init(k2, c, cross=c.family == "encdec"),
        "final_norm": norm_init(c),
    }
    if c.family == "encdec":
        p["encoder"] = {
            "layers": blocks.enc_stack_init(k3, c),
            "norm": norm_init(c),
            # learned positions for encoder frames
            "pos": (jax.random.normal(k4, (c.enc_seq, c.d_model), jnp.float32)
                    * 0.02).astype(jnp.dtype(c.param_dtype)),
        }
    return p


def init_abstract(c: ModelConfig) -> Params:
    """Shape-only params (no allocation) — used by the dry-run."""
    return jax.eval_shape(lambda k: init(k, c), jax.random.key(0))


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------


def _inputs_to_embeds(c: ModelConfig, p: Params, tokens: jax.Array,
                      patch_embeds: Optional[jax.Array],
                      pos_offset: int = 0) -> jax.Array:
    b, s_text = tokens.shape
    positions = jnp.arange(s_text)[None, :] + pos_offset
    x = embed_tokens(c, p["embed"], tokens, positions)
    if c.family == "vlm" and patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    return x


def encode(c: ModelConfig, p: Params, frames: jax.Array,
           unroll: bool = False):
    """Whisper-backbone encoder over precomputed frame embeddings.

    frames: (B, T_enc, D) — the conv frontend is a stub (precomputed).
    Returns encoder output and stacked per-decoder-layer cross K/V.
    """
    enc = p["encoder"]
    x = frames.astype(dtype_of(c)) + enc["pos"][None].astype(dtype_of(c))

    def body(x, layer):
        h = apply_norm(c, layer["norm1"], x)
        x = x + attn.self_attention(c, layer["attn"], h, causal=False)
        from repro.models.common import apply_mlp
        x = x + apply_mlp(c, layer["mlp"], apply_norm(c, layer["norm2"], x))
        return x, None

    # remat: without it the backward saves every encoder layer's O(T^2)
    # softmax internals (measured 15+ GiB on whisper train_4k)
    x, _ = jax.lax.scan(jax.checkpoint(body, policy=None), x, enc["layers"],
                        unroll=unroll)
    enc_out = apply_norm(c, enc["norm"], x)

    # Per-decoder-layer cross-attention K/V (stacked like the layer params)
    def kv_body(_, period_params):
        ekv = {}
        for i in range(blocks.period_of(c)):
            sp = period_params[f"slot{i}"]
            k, v = attn.encoder_kv(c, sp["cross"], enc_out)
            ekv[f"slot{i}"] = {"k": k, "v": v}
        return None, ekv

    _, enc_kv = jax.lax.scan(kv_body, None, p["layers"], unroll=unroll)
    return enc_out, enc_kv


# ---------------------------------------------------------------------------
# Training forward
# ---------------------------------------------------------------------------


def forward(c: ModelConfig, p: Params, tokens: jax.Array, *,
            patch_embeds: Optional[jax.Array] = None,
            enc_frames: Optional[jax.Array] = None,
            impl: str = "repeat", remat: str = "full", unroll: bool = False):
    """Full causal forward. Returns (logits, aux_loss).

    vlm:    logits cover only the text positions (patches are prefix).
    encdec: enc_frames (B, T_enc, D) must be provided.
    """
    x = _inputs_to_embeds(c, p, tokens, patch_embeds)
    enc_kv = None
    if c.family == "encdec":
        assert enc_frames is not None
        _, enc_kv = encode(c, p, enc_frames, unroll=unroll)
    x, aux = blocks.stack_forward(c, p["layers"], x, causal=True, impl=impl,
                                  remat=remat, enc_kv_stacked=enc_kv,
                                  unroll=unroll)
    x = apply_norm(c, p["final_norm"], x)
    if c.family == "vlm" and patch_embeds is not None:
        x = x[:, patch_embeds.shape[1]:]
    logits = unembed(c, p["embed"], x)
    return logits, aux


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(c: ModelConfig, p: Params, tokens: jax.Array, *,
            patch_embeds: Optional[jax.Array] = None,
            enc_frames: Optional[jax.Array] = None, impl: str = "repeat",
            unroll: bool = False, last_pos: Optional[jax.Array] = None,
            prefix_kv: Params = None, pos_offset: int = 0,
            paged_prefix: Params = None,
            paged_tables: Optional[jax.Array] = None,
            paged_impl: str = "xla", paged_interpret: bool = False,
            valid_len: Optional[jax.Array] = None,
            carry_slots: Optional[jax.Array] = None,
            return_routed: bool = False):
    """Process the prompt; return (last-position logits, caches, enc_kv).

    ``last_pos`` (B,) int32 overrides which position's logits are
    returned per row — the batched serve prefill right-pads prompts to a
    shared length bucket and reads each request's logits at its *true*
    last token (pad rows are never attended: causal masking hides them
    from real tokens, and decode overwrites them in place).

    ``prefix_kv`` + ``pos_offset`` select the prefix-cached *suffix*
    prefill: ``tokens`` holds only the suffix (global positions start at
    ``pos_offset``), ``prefix_kv`` is the stacked per-layer K/V of the
    cached ``pos_offset``-token prefix (see ``blocks.stack_prefill``),
    and the returned ``caches`` cover only the suffix.

    ``paged_prefix`` + ``paged_tables`` are the paged twin: the pool
    cache tree itself and the (B, npre) prefix block table — the prefix
    KV stays in the pool and attention walks the table via the paged
    prefill kernel (``paged_impl``/``paged_interpret`` select the
    xla-ref vs Pallas vs interpret dispatch). ``carry_slots`` (B,) names
    the pool rows whose recurrent state mamba mixers resume from.

    ``valid_len`` (B,) int32 counts each row's real tokens (see
    ``blocks.stack_prefill``). ``return_routed`` appends the int32 count
    of real-token choices the dropless expert layers computed.
    """
    assert (prefix_kv is None) or (paged_prefix is None)
    has_prefix = (prefix_kv is not None) or (paged_prefix is not None)
    assert has_prefix == (pos_offset > 0), (pos_offset,)
    x = _inputs_to_embeds(c, p, tokens, patch_embeds, pos_offset=pos_offset)
    enc_kv = None
    if c.family == "encdec":
        _, enc_kv = encode(c, p, enc_frames, unroll=unroll)
    positions = None
    if has_prefix:
        positions = jnp.arange(tokens.shape[1])[None, :] + pos_offset
    x, caches, routed = blocks.stack_prefill(
        c, p["layers"], x, impl=impl, enc_kv_stacked=enc_kv,
        prefix_kv=prefix_kv, paged_prefix=paged_prefix,
        paged_tables=paged_tables, paged_impl=paged_impl,
        paged_interpret=paged_interpret, positions=positions, unroll=unroll,
        valid_len=valid_len, carry_slots=carry_slots)
    if last_pos is not None:
        x_last = jnp.take_along_axis(
            x, last_pos.astype(jnp.int32)[:, None, None], axis=1)
    else:
        x_last = x[:, -1:]
    x_last = apply_norm(c, p["final_norm"], x_last)
    logits = unembed(c, p["embed"], x_last)
    if return_routed:
        return logits, caches, enc_kv, _count(routed)
    return logits, caches, enc_kv


def _count(routed) -> jax.Array:
    return jnp.zeros((), jnp.int32) if routed is None else routed


def decode_step(c: ModelConfig, p: Params, token: jax.Array, caches: Params,
                pos: jax.Array, *, enc_kv: Params = None,
                impl: str = "grouped", unroll: bool = False,
                block_tables: Optional[jax.Array] = None,
                n_kv_blocks: Optional[int] = None,
                paged_impl: str = "xla", paged_interpret: bool = False,
                live: Optional[jax.Array] = None,
                return_routed: bool = False):
    """token: (B, 1) int32; pos: scalar int32 OR per-row (B,) int32 (the
    continuous-batching engine decodes slots at independent positions).
    ``block_tables`` switches the attention layers onto the paged KV
    pool (see ``blocks.stack_decode``); ``live`` (B,) bool marks the rows
    that decode a real token. Returns (logits, caches), and the routed
    count (see :func:`prefill`) with ``return_routed``."""
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] if pos.ndim == 1 else jnp.full_like(token, pos)
    x = embed_tokens(c, p["embed"], token, positions)
    x, caches, routed = blocks.stack_decode(
        c, p["layers"], x, caches, pos, impl=impl, enc_kv_stacked=enc_kv,
        unroll=unroll, block_tables=block_tables, n_kv_blocks=n_kv_blocks,
        paged_impl=paged_impl, paged_interpret=paged_interpret, live=live)
    x = apply_norm(c, p["final_norm"], x)
    logits = unembed(c, p["embed"], x)
    if return_routed:
        return logits, caches, _count(routed)
    return logits, caches
