"""Attention: GQA / MHA, causal + windowed + cross, train/prefill/decode.

Two einsum formulations are provided, selected by the sharding plan:
  - "repeat":  KV heads repeated to H query heads; shards the H dim over the
               TP axis when ``n_heads % tp == 0`` (Megatron-style head TP).
  - "grouped": (Kh, G) grouped einsum; avoids materializing repeated KV and
               shards Kh when divisible, else replicates head compute.

The Pallas flash-attention kernel (repro.kernels) implements the same
contract for TPU; the XLA path here is the oracle and the dry-run path.
"""
from __future__ import annotations

import contextlib
from contextvars import ContextVar
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (
    apply_rope, dense_init, dtype_of, opt_barrier, param_dtype_of,
)

Params = Any
NEG_INF = float(jnp.finfo(jnp.float32).min)


# ---------------------------------------------------------------------------
# Sharding hints. GSPMD propagates layouts well in the forward pass but
# loses them inside remat (jax.checkpoint) recomputation in the backward
# while-loop — measured to replicate attention and all-reduce O(S*T) score
# tensors (EXPERIMENTS.md par.Perf). Explicit constraints on q/k/v/out pin
# the layout in both passes. The launch layer installs per-plan hints; with
# no hints installed (single-device tests) everything is a no-op.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AttnShardingHints:
    q_spec: Any = None        # (B, S, H, Dh)
    kv_spec: Any = None       # (B, T, Kh, Dh)
    out_spec: Any = None      # (B, S, H, Dh) post-attention
    cache_spec: Any = None    # decode KV cache (B, T, Kh, Dh)
    resid_spec: Any = None    # residual stream (B, S, D) — forces the
    #                           Megatron block all-reduce to happen in bf16
    #                           (before the fp32 norm), halving AR wire bytes


_HINTS: ContextVar[Optional[AttnShardingHints]] = ContextVar(
    "attn_sharding_hints", default=None)

# Perf-probe: replace the attention CORE (scores+softmax+pv) with zeros,
# keeping projections — compiling with/without isolates attention's
# contribution to the roofline terms (used by the hillclimb driver).
_SKIP_CORE: ContextVar[bool] = ContextVar("attn_skip_core", default=False)


@contextlib.contextmanager
def skip_attention_core():
    tok = _SKIP_CORE.set(True)
    try:
        yield
    finally:
        _SKIP_CORE.reset(tok)


@contextlib.contextmanager
def sharding_hints(hints: Optional[AttnShardingHints]):
    tok = _HINTS.set(hints)
    try:
        yield
    finally:
        _HINTS.reset(tok)


def _hint(x: jax.Array, which: str) -> jax.Array:
    h = _HINTS.get()
    spec = getattr(h, which, None) if h is not None else None
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def attn_init(key, c: ModelConfig) -> Params:
    pd = param_dtype_of(c)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], c.d_model, (c.n_heads, c.d_head), pd),
        "wk": dense_init(ks[1], c.d_model, (c.n_kv_heads, c.d_head), pd),
        "wv": dense_init(ks[2], c.d_model, (c.n_kv_heads, c.d_head), pd),
        # stored (H, Dh, D): contraction over (H, Dh)
        "wo": dense_init(ks[3], c.n_heads * c.d_head, c.d_model, pd).reshape(
            c.n_heads, c.d_head, c.d_model),
    }
    if c.qkv_bias:
        p["bq"] = jnp.zeros((c.n_heads, c.d_head), pd)
        p["bk"] = jnp.zeros((c.n_kv_heads, c.d_head), pd)
        p["bv"] = jnp.zeros((c.n_kv_heads, c.d_head), pd)
    return p


def qkv_proj(c: ModelConfig, p: Params, x: jax.Array,
             positions: Optional[jax.Array] = None):
    """x: (B, S, D) -> q (B,S,H,Dh), k/v (B,S,Kh,Dh) with RoPE applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if c.positions == "rope" and positions is not None:
        # barrier: keep the f32 rope math from retroactively upcasting the
        # projection matmuls (and thus the stacked weights) to f32
        q, k = opt_barrier((q, k))
        q = apply_rope(q, positions, c.rope_theta)
        k = apply_rope(k, positions, c.rope_theta)
    return _hint(q, "q_spec"), _hint(k, "kv_spec"), _hint(v, "kv_spec")


def _mask_bias(mask: jax.Array, dtype) -> jax.Array:
    return jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)


def make_causal_mask(s: int, t: int, window: Optional[int] = None,
                     q_offset: int | jax.Array = 0) -> jax.Array:
    """(s, t) boolean mask. Query i (global pos q_offset+i) sees key j<=pos."""
    qpos = jnp.arange(s)[:, None] + q_offset
    kpos = jnp.arange(t)[None, :]
    m = kpos <= qpos
    if window is not None:
        m = m & (kpos > qpos - window)
    return m


def score_scale(c: ModelConfig) -> Optional[float]:
    """The configuration's attention multiplier, or None for the usual
    ``1 / sqrt(d_head)`` (which every attention path computes itself)."""
    return c.attn_mult or None


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: Optional[jax.Array],
         impl: str = "repeat", scale: Optional[float] = None) -> jax.Array:
    """Scaled dot-product attention.

    q: (B,S,H,Dh); k,v: (B,T,Kh,Dh); mask: broadcastable to (B,1,S,T) or None
    (None = full bidirectional). fp32 softmax. ``scale`` multiplies the
    scores (default ``1 / sqrt(Dh)``).
    """
    b, s, h, dh = q.shape
    kh = k.shape[2]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32)).astype(q.dtype)
    else:
        scale = jnp.asarray(scale, q.dtype)
    q = q * scale
    if impl == "repeat" or h == kh:
        if h != kh:
            rep = h // kh
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        scores = opt_barrier(
            jnp.einsum("bshk,bthk->bhst", q, k)).astype(jnp.float32)
        if mask is not None:
            scores = scores + _mask_bias(mask, scores.dtype)
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhst,bthk->bshk", w, v)
    else:
        g = h // kh
        qg = q.reshape(b, s, kh, g, dh)
        scores = opt_barrier(
            jnp.einsum("bskgd,btkd->bkgst", qg, k)).astype(jnp.float32)
        if mask is not None:
            scores = scores + _mask_bias(mask, scores.dtype)[:, None]
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgst,btkd->bskgd", w, v).reshape(b, s, h, dh)
    return out


def out_proj(p: Params, attn_out: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", attn_out, p["wo"])


# Score tensors larger than this (elements, per device-unaware global view)
# switch to the memory-bounded q-chunked path.
CHUNK_THRESHOLD = 1 << 31
Q_CHUNK = 1024


def sdpa_chunked_q(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool, window: Optional[int], impl: str,
                   q_chunk: int = Q_CHUNK, unroll: bool = False,
                   scale: Optional[float] = None) -> jax.Array:
    """Flash-style memory-bounded attention: scan over query chunks.

    Each chunk materializes only a (B, H, q_chunk, T_vis) score block —
    with causal+windowed masks the visible T is additionally sliced, making
    windowed attention honestly sub-quadratic. This is the XLA analog of
    the Pallas flash kernel (repro.kernels) used on real TPU.
    """
    b, s, h, dh = q.shape
    t = k.shape[1]
    nq = s // q_chunk
    assert s % q_chunk == 0, (s, q_chunk)
    qc = q.reshape(b, nq, q_chunk, h, dh).transpose(1, 0, 2, 3, 4)

    def chunk(i, qi):
        qi = _hint(qi, "q_spec")
        start = i * q_chunk
        if causal and window is None:
            # keys visible to this chunk: [0, start + q_chunk)
            t_vis = t  # static bound; mask handles the tail
            mask = make_causal_mask(q_chunk, t_vis, None, q_offset=start)
            return _hint(sdpa(qi, k, v, mask[None, None], impl=impl,
                              scale=scale), "out_spec")
        if causal and window is not None:
            w = min(window, t)
            vis = min(q_chunk + w, t)
            k_start = jnp.clip(start + q_chunk - vis, 0, t - vis)
            ks = jax.lax.dynamic_slice_in_dim(k, k_start, vis, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, k_start, vis, axis=1)
            qpos = start + jnp.arange(q_chunk)[:, None]
            kpos = k_start + jnp.arange(vis)[None, :]
            mask = (kpos <= qpos) & (kpos > qpos - window)
            return _hint(sdpa(qi, ks, vs, mask[None, None], impl=impl,
                              scale=scale), "out_spec")
        return _hint(sdpa(qi, k, v, None, impl=impl, scale=scale),
                     "out_spec")

    # Remat each chunk: backward recomputes the chunk's scores instead of
    # saving fp32 softmax residuals stacked across all chunks (this is the
    # flash-attention backward strategy, in XLA form).
    chunk = jax.checkpoint(chunk, policy=None)

    def body(_, inp):
        i, qi = inp
        return None, chunk(i, qi)

    _, out = jax.lax.scan(body, None, (jnp.arange(nq), qc), unroll=unroll)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dh)


def _score_elems(c: ModelConfig, s: int, t: int) -> int:
    return c.n_heads * s * t


def attend(c: ModelConfig, q, k, v, *, causal: bool, impl: str,
           unroll: bool = False) -> jax.Array:
    """Select full vs q-chunked attention by score-tensor size.

    q_chunk is pass-adaptive: the metrics pass (unroll=True) uses few big
    chunks so the unrolled HLO stays compilable; the real/memory pass uses
    small chunks so the live score block is tightly bounded.
    """
    b, s = q.shape[:2]
    t = k.shape[1]
    if _SKIP_CORE.get():
        return jnp.zeros_like(q) + 0.0 * (jnp.sum(k[:, :1]) + jnp.sum(v[:, :1])).astype(q.dtype)
    big = b * _score_elems(c, s, t) > CHUNK_THRESHOLD
    q_chunk = max(s // 8, Q_CHUNK) if unroll else 256
    if big and s % q_chunk == 0:
        return sdpa_chunked_q(q, k, v, causal=causal, window=c.attn_window,
                              impl=impl, q_chunk=q_chunk, unroll=unroll,
                              scale=score_scale(c))
    mask = None
    if causal:
        mask = make_causal_mask(s, t, c.attn_window)[None, None]
    return sdpa(q, k, v, mask, impl=impl, scale=score_scale(c))


# ---------------------------------------------------------------------------
# Full attention ops used by the blocks
# ---------------------------------------------------------------------------


def self_attention(c: ModelConfig, p: Params, x: jax.Array, *,
                   causal: bool = True, positions: Optional[jax.Array] = None,
                   impl: str = "repeat", unroll: bool = False) -> jax.Array:
    """Training/encoding self-attention over the full sequence."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = qkv_proj(c, p, x, positions if c.positions == "rope" else None)
    return out_proj(p, attend(c, q, k, v, causal=causal, impl=impl,
                              unroll=unroll))


def cross_attention(c: ModelConfig, p: Params, x: jax.Array,
                    enc_kv: tuple[jax.Array, jax.Array],
                    impl: str = "repeat") -> jax.Array:
    """Decoder cross-attention against precomputed encoder K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    k, v = enc_kv
    return out_proj(p, sdpa(q, k, v, None, impl=impl, scale=score_scale(c)))


def encoder_kv(c: ModelConfig, p: Params, enc_out: jax.Array):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    return k, v


def prefill_attention(c: ModelConfig, p: Params, x: jax.Array, *,
                      positions: Optional[jax.Array] = None,
                      impl: str = "repeat", unroll: bool = False,
                      prefix_kv: Optional[tuple] = None,
                      paged_prefix: Optional[tuple] = None):
    """Causal self-attention that also returns the K/V cache.

    ``prefix_kv`` = (pk, pv), each (B, T_pre, Kh, Dh): precomputed KV of
    a cached prompt prefix (prefix-cached suffix prefill). The queries
    are the *suffix* tokens at global positions ``T_pre + i`` (the
    caller passes RoPE ``positions`` with the offset applied); they
    attend over [prefix KV ++ suffix KV] under the causal mask shifted
    by ``q_offset=T_pre``. Only the suffix (k, v) is returned for the
    cache — the prefix blocks already live in the pool.

    ``paged_prefix`` = (k_pool, v_pool, k_scale, v_scale, tables,
    paged_impl, paged_interpret): same semantics, but the prefix KV
    stays IN the paged pool — ``kernels.ops.paged_prefill_attention``
    walks the slot's block table directly (scales non-None mark an int8
    pool, dequantized inside the kernel's KV load). Replaces the dense
    ``k_pool[tables]`` gather the engine used to do.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = qkv_proj(c, p, x, positions if c.positions == "rope" else None)
    assert prefix_kv is None or paged_prefix is None
    if paged_prefix is not None:
        from repro.kernels import ops as _kops
        k_pool, v_pool, k_scale, v_scale, tables, pimpl, pinterp = paged_prefix
        out = _kops.paged_prefill_attention(
            q, k, v, k_pool, v_pool, tables, window=c.attn_window,
            impl=pimpl, interpret=pinterp, k_scale=k_scale, v_scale=v_scale,
            scale=score_scale(c))
        return out_proj(p, out.astype(q.dtype)), (k, v)
    if prefix_kv is not None:
        pk, pv = prefix_kv
        t_pre = pk.shape[1]
        k_full = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        mask = make_causal_mask(s, t_pre + s, c.attn_window,
                                q_offset=t_pre)[None, None]
        out = out_proj(p, sdpa(q, k_full, v_full, mask, impl=impl,
                               scale=score_scale(c)))
        return out, (k, v)
    out = out_proj(p, attend(c, q, k, v, causal=True, impl=impl,
                             unroll=unroll))
    return out, (k, v)


def _quantized_block_write(pool: jax.Array, scale: jax.Array,
                           new: jax.Array, layer: jax.Array, blk: jax.Array,
                           off: jax.Array):
    """Write one token (B, Kh, Dh) into the int8 pool blocks
    ``(layer, blk[b])`` at offset ``off[b]`` of the stacked pool
    (L, n_blocks, bs, Kh, Dh), preserving the per-(block, head)
    symmetric scale invariant: dequantize the owning block, place the
    token, re-quantize under ``max(old_scale, maxabs(new)/127)``. The
    scale is MONOTONE, so when the new token fits the old range the
    block's other int8 codes are bit-unchanged (round(i*s/s) == i).
    Duplicate ``blk`` entries only ever occur on the trash block 0 (idle
    slots), where the undefined scatter order is harmless."""
    newf = new.astype(jnp.float32)
    osc = scale[layer, blk].astype(jnp.float32)                  # (B, Kh)
    deq = pool[layer, blk].astype(jnp.float32) \
        * osc[:, None, :, None]                                  # (B,bs,Kh,Dh)
    rows = jnp.arange(new.shape[0])
    deq = deq.at[rows, off].set(newf)
    nsc = jnp.maximum(osc, jnp.max(jnp.abs(newf), axis=-1) / 127.0)
    q = jnp.round(deq / jnp.where(nsc > 0.0, nsc, 1.0)[:, None, :, None])
    q = jnp.clip(q, -127, 127).astype(pool.dtype)
    return (pool.at[layer, blk].set(q, mode="drop"),
            scale.at[layer, blk].set(nsc.astype(scale.dtype), mode="drop"))


def decode_attention(c: ModelConfig, p: Params, x: jax.Array,
                     cache_k: jax.Array, cache_v: jax.Array,
                     pos: jax.Array, *, impl: str = "grouped",
                     block_tables: Optional[jax.Array] = None,
                     n_kv_blocks: Optional[int] = None,
                     paged_impl: str = "xla",
                     paged_interpret: bool = False,
                     cache_k_scale: Optional[jax.Array] = None,
                     cache_v_scale: Optional[jax.Array] = None,
                     layer: Optional[jax.Array] = None):
    """One-token decode against a fixed-size KV cache.

    x: (B, 1, D); cache_k/v: (B, T, Kh, Dh); pos: scalar int32 (step
    index, shared by all rows) OR an int32 vector (B,) of per-row
    positions — the continuous-batching serve engine tracks an
    independent write position per slot.
    Returns (out (B,1,D), new_cache_k, new_cache_v).

    Scalar pos + windowed attention slices the cache to the last
    ``window`` entries (O(window) per step); otherwise the new token
    attends to all cached positions <= pos under a (per-row) mask
    (O(T) per step — linear, not quadratic).

    Paged path (``block_tables`` given): cache_k/v are the *stacked
    shared block pools* ``(L, n_blocks, bs, Kh, Dh)`` of every layer,
    ``layer`` the int32 index of this one, and ``block_tables`` the
    ``(B, max_blocks)`` per-slot table (``serve.cache.PagedKVCache``).
    The new token is scattered into its slot's current block of this
    layer (one scatter at ``[layer, blk, off]``, in place: the pool is
    never sliced per layer); attention walks only the first
    ``n_kv_blocks`` (static — the engine buckets it to the longest live
    slot) table columns of the layer via
    ``kernels.ops.paged_decode_attention``, masked by true per-slot
    length — never the ``max_len``-padded row. ``pos`` must be the
    per-slot vector; idle slots park at a position whose table column is
    the trash block 0. The new stacked pools are returned.

    ``cache_k_scale``/``cache_v_scale`` (L, n_blocks, Kh) f32 mark an
    int8 pool: the token write goes through
    :func:`_quantized_block_write` and the return value grows to a
    5-tuple ``(out, cache_k, cache_v, cache_k_scale, cache_v_scale)``.
    """
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    positions = pos[:, None] if per_slot else jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = qkv_proj(c, p, x, positions if c.positions == "rope" else None)

    if block_tables is not None:
        assert per_slot, "paged decode requires per-slot positions"
        from repro.kernels import ops as _kops
        bs_blk = cache_k.shape[2]
        nb = n_kv_blocks if n_kv_blocks is not None else block_tables.shape[1]
        blk = jnp.take_along_axis(block_tables, pos[:, None] // bs_blk,
                                  axis=1)[:, 0]
        off = pos % bs_blk
        quantized = cache_k_scale is not None
        if quantized:
            cache_k, cache_k_scale = _quantized_block_write(
                cache_k, cache_k_scale, k_new[:, 0], layer, blk, off)
            cache_v, cache_v_scale = _quantized_block_write(
                cache_v, cache_v_scale, v_new[:, 0], layer, blk, off)
        else:
            cache_k = cache_k.at[layer, blk, off].set(
                k_new[:, 0].astype(cache_k.dtype), mode="drop")
            cache_v = cache_v.at[layer, blk, off].set(
                v_new[:, 0].astype(cache_v.dtype), mode="drop")
        out = _kops.paged_decode_attention(
            q[:, 0], cache_k, cache_v, block_tables[:, :nb], pos + 1,
            layer=layer, window=c.attn_window, impl=paged_impl,
            interpret=paged_interpret, k_scale=cache_k_scale,
            v_scale=cache_v_scale, scale=score_scale(c))
        out = out_proj(p, out[:, None].astype(q.dtype))
        if quantized:
            return out, cache_k, cache_v, cache_k_scale, cache_v_scale
        return out, cache_k, cache_v

    if per_slot:
        # independent write position per batch row (slot): row scatter
        rows = jnp.arange(b)
        cache_k = cache_k.at[rows, pos].set(k_new[:, 0])
        cache_v = cache_v.at[rows, pos].set(v_new[:, 0])
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new, pos,
                                                      axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new, pos,
                                                      axis=1)

    cache_k = _hint(cache_k, "cache_spec")
    cache_v = _hint(cache_v, "cache_spec")
    t = cache_k.shape[1]
    if (not per_slot and c.attn_window is not None and c.attn_window < t):
        w = c.attn_window
        start = jnp.clip(pos - w + 1, 0, t - w)
        k_att = jax.lax.dynamic_slice_in_dim(cache_k, start, w, axis=1)
        v_att = jax.lax.dynamic_slice_in_dim(cache_v, start, w, axis=1)
        kpos = start + jnp.arange(w)
        mask = (kpos <= pos)[None, None, None, :]  # (1,1,1,W)
    else:
        k_att, v_att = cache_k, cache_v
        kpos = jnp.arange(t)
        if per_slot:
            m = kpos[None, :] <= positions  # (B, T)
            if c.attn_window is not None and c.attn_window < t:
                # per-row starts preclude a shared slice; mask instead
                m = m & (kpos[None, :] > positions - c.attn_window)
            mask = m[:, None, None, :]  # (B,1,1,T)
        else:
            mask = (kpos <= pos)[None, None, None, :]  # (1,1,1,T)
    out = out_proj(p, sdpa(q, k_att, v_att, mask, impl=impl,
                           scale=score_scale(c)))
    return out, cache_k, cache_v
