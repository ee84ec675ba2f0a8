"""Pallas TPU paged prefill flash attention (chunk of queries vs a block
table + the chunk's own suffix KV).

The serve prefill hot path that decode's paged kernel left behind:
chunked prefill (every chunk after the first) and prefix-cached suffix
prefill both attend a ``Sq``-token query slice against [the slot's first
``npre`` pool blocks ++ the slice's own fresh KV]. Until this kernel,
that ran as a dense XLA gather (``k_pool[tables]`` materialized per
layer) followed by masked SDPA; here the prefix KV never leaves the
pool.

TPU-native design (the ``decode_attention.py`` block-table walk fused
with the ``flash_attention.py`` online-softmax Q loop):
  - grid ``(B, nQ, npre + nS)``; the KV dimension is innermost, which
    Pallas TPU executes SEQUENTIALLY per core, so the online-softmax
    running state (m, l, acc) of every KV head lives in VMEM scratch and
    is carried across a query tile's prefix blocks and suffix tiles;
  - KV blocks and suffix tiles carry all KV heads (``(1, bs, Kh, Dh)``,
    ``(1, bk, Kh, Dh)``) so their last two dims equal the arrays' —
    Mosaic's rule for blocks — and the kernel walks heads in a static
    loop; query tiles are multiples of 8 rows (or the whole chunk);
  - the block table rides in as **scalar prefetch**
    (``pltpu.PrefetchScalarGridSpec``): for KV step ``j < npre`` the
    k/v BlockSpec index map reads ``tables[b, j]`` and DMAs the
    *physical* pool block — the paged indirection costs one SMEM
    lookup, not a gather; steps ``j >= npre`` stream the suffix KV
    tiles ``(j - npre)`` from the freshly projected k/v instead;
  - GQA is expressed in the q layout: q is viewed as
    ``(B, Kh, G, Sq, Dh)`` so the ``G = H // Kh`` query heads sharing
    a KV head are one MXU operand; repeated KV is never materialized;
  - causal masking is positional with the chunk's global offset
    ``pos_offset = npre * bs`` folded in: prefix blocks sit entirely
    below every query position (prefixes are whole blocks of real
    tokens), so only the sliding window can exclude them; suffix tiles
    beyond the causal diagonal — and blocks/tiles outside the window —
    are skipped with ``pl.when`` (no MXU work);
  - int8 KV pools dequantize inside the load: per-block-per-head
    symmetric scales ``(n_blocks, Kh)`` ride in through SMEM, one
    block's ``(1, 1, Kh)`` row per grid step addressed by the same table
    lookup, and ``k * scale`` happens on the VMEM tile — fp prefix KV is
    never materialized anywhere.

Validated against ``kernels.ref.paged_prefill_attention_ref`` in
interpret mode (tests sweep shapes, block sizes, GQA groups, prefix
depths / pos_offset, shuffled tables, windows, dtypes, int8 scales).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _prefill_kernel(tables_ref, *refs, scale: float, bs: int, bq: int,
                    bk: int, kh: int, npre: int, n_kv: int, pos_offset: int,
                    window: Optional[int], quantized: bool):
    if quantized:
        (q_ref, kp_ref, vp_ref, ksc_ref, vsc_ref, ks_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, kp_ref, vp_ref, ks_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    iq = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first_q = pos_offset + iq * bq
    last_q = first_q + bq - 1

    def accum(h, k, v, kpos0):
        """Online-softmax update of KV head ``h`` with one KV tile (k/v:
        (tile, Dh) f32, covering global positions [kpos0, kpos0 + tile))."""
        q = q_ref[0, h].astype(jnp.float32) * scale             # (G, bq, Dh)
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())))  # (G, bq, t)
        qpos = first_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = kpos <= qpos
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[h]                                        # (G, bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())))
        acc_scr[h] = acc_scr[h] * alpha + pv
        m_scr[h] = m_new

    # prefix pool blocks: whole blocks of real tokens strictly below
    # pos_offset <= first_q, so causality never excludes them — only
    # the sliding window can.
    run_pre = j < npre
    if window is not None:
        run_pre = jnp.logical_and(run_pre, (j + 1) * bs - 1 > first_q - window)

    @pl.when(run_pre)
    def _pool_block():
        for h in range(kh):       # static unroll over the block's KV heads
            k = kp_ref[0, :, h, :].astype(jnp.float32)           # (bs, Dh)
            v = vp_ref[0, :, h, :].astype(jnp.float32)
            if quantized:
                k = k * ksc_ref[0, 0, h]
                v = v * vsc_ref[0, 0, h]
            accum(h, k, v, j * bs)

    # suffix tiles: global start pos_offset + (j - npre) * bk; tiles
    # past the causal diagonal of this q tile are skipped.
    first_k = pos_offset + (j - npre) * bk
    run_suf = jnp.logical_and(j >= npre, first_k <= last_q)
    if window is not None:
        run_suf = jnp.logical_and(run_suf, first_k + bk - 1 > first_q - window)

    @pl.when(run_suf)
    def _suffix_tile():
        for h in range(kh):
            accum(h, ks_ref[0, :, h, :].astype(jnp.float32),
                  vs_ref[0, :, h, :].astype(jnp.float32), first_k)

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _tile(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is at most ``cap`` and a multiple of
    8 (Mosaic's sublane rule for the second-to-last block dim), else
    ``n`` itself (a block equal to the whole dim is always legal)."""
    for t in range(min(cap, n), 7, -1):
        if n % t == 0 and t % 8 == 0:
            return t
    return n


def paged_prefill_attention(q: jax.Array, k_suffix: jax.Array,
                            v_suffix: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, tables: jax.Array, *,
                            window: Optional[int] = None,
                            scale: Optional[float] = None,
                            k_scale: Optional[jax.Array] = None,
                            v_scale: Optional[jax.Array] = None,
                            block_q: int = 128, block_k: int = 128,
                            interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, Dh); k/v_suffix: (B, Sq, Kh, Dh) — the chunk's own
    freshly projected KV; k/v_pool: (n_blocks, bs, Kh, Dh) — the shared
    paged pool (int8 when k/v_scale (n_blocks, Kh) f32 are given);
    tables: (B, npre) int32 physical ids of each row's prefix blocks in
    position order. Queries sit at global positions
    ``pos_offset + i`` with ``pos_offset = npre * bs`` (prefixes are
    whole blocks). Returns (B, Sq, H, Dh) in q.dtype.
    """
    b, sq, h, dh = q.shape
    n_blocks, bs, kh = k_pool.shape[:3]
    assert h % kh == 0, (h, kh)
    assert k_suffix.shape == (b, sq, kh, dh), (k_suffix.shape, (b, sq, kh, dh))
    assert (k_scale is None) == (v_scale is None)
    g = h // kh
    npre = tables.shape[1]
    assert npre >= 1, "paged prefill needs >= 1 prefix block (cold " \
        "prefill with no prefix takes the dense path)"
    pos_offset = npre * bs
    # tiles divide Sq exactly (engine buckets are block_size multiples,
    # so this lands on a large divisor — e.g. Sq=144 with block_q=128
    # tiles at 72)
    bq = _tile(sq, block_q)
    bk = _tile(sq, block_k)
    n_q, n_suf = sq // bq, sq // bk
    n_kv = npre + n_suf
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    quantized = k_scale is not None

    kernel = functools.partial(
        _prefill_kernel, scale=scale, bs=bs, bq=bq, bk=bk, kh=kh, npre=npre,
        n_kv=n_kv, pos_offset=pos_offset, window=window, quantized=quantized)

    def q_index(bi, iq, j, tables_ref):
        return (bi, 0, 0, iq, 0)

    def pool_index(bi, iq, j, tables_ref):
        # j >= npre clamps to the last prefix entry: a valid (never
        # computed-on) block, so the dead DMA cannot fault.
        return (tables_ref[bi, jnp.minimum(j, npre - 1)], 0, 0, 0)

    def scale_index(bi, iq, j, tables_ref):
        return (tables_ref[bi, jnp.minimum(j, npre - 1)], 0, 0)

    def suffix_index(bi, iq, j, tables_ref):
        return (bi, jnp.maximum(j - npre, 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, kh, g, bq, dh), q_index),
        pl.BlockSpec((1, bs, kh, dh), pool_index),
        pl.BlockSpec((1, bs, kh, dh), pool_index),
    ]
    operands = [q.transpose(0, 2, 1, 3).reshape(b, kh, g, sq, dh),
                k_pool, v_pool]
    if quantized:
        # (n_blocks, 1, Kh): the last two block dims then equal the
        # array's, and SMEM holds one block's scales per grid step
        in_specs += [pl.BlockSpec((1, 1, kh), scale_index,
                                  memory_space=pltpu.SMEM)] * 2
        operands += [s.astype(jnp.float32).reshape(n_blocks, 1, kh)
                     for s in (k_scale, v_scale)]
    in_specs += [
        pl.BlockSpec((1, bk, kh, dh), suffix_index),
        pl.BlockSpec((1, bk, kh, dh), suffix_index),
    ]
    operands += [k_suffix, v_suffix]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_q, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kh, g, bq, dh), q_index),
        scratch_shapes=[
            pltpu.VMEM((kh, g, bq, 1), jnp.float32),
            pltpu.VMEM((kh, g, bq, 1), jnp.float32),
            pltpu.VMEM((kh, g, bq, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, sq, dh), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), *operands)
    return out.reshape(b, h, sq, dh).transpose(0, 2, 1, 3)
