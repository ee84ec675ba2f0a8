"""Pallas TPU paged decode attention (single-token GQA over a block pool).

The serve decode hot path: each sequence holds one new query token and a
*paged* KV history — fixed-size blocks scattered through a shared pool,
addressed by a per-sequence block table (``serve.cache.PagedKVCache``).
The kernel walks only the table, never a dense ``(B, max_len)`` cache
row, so attention work scales with the tokens a sequence actually owns
instead of the padded slot capacity.

TPU-native design (mirrors ``flash_attention.py``):
  - grid ``(B, nb)``; the block dimension is innermost, which Pallas
    TPU executes SEQUENTIALLY per core, so the online-softmax running
    state (m, l, acc) of every KV head lives in VMEM scratch and is
    carried across the sequence's blocks;
  - each grid step DMAs one whole pool block, all KV heads
    ``(1, bs, Kh, Dh)``: its last two dims equal the pool's, which is
    what Mosaic requires of a block whose head slice (1 row) is not a
    multiple of 8. The kernel walks the heads in a static loop;
  - the block table, true lengths and the layer ride in as **scalar
    prefetch** arguments (``pltpu.PrefetchScalarGridSpec``): the k/v
    BlockSpec index map reads ``(layer, tables[b, j])`` to DMA the
    *physical* pool block of that layer straight out of the stacked
    ``(L, n_blocks, ...)`` pool — the paged indirection costs one SMEM
    lookup, not a gather, and the decode program never slices a layer's
    pool out (it updates the stacked pool in place);
  - GQA is expressed in the q layout: q is viewed as ``(B, Kh, G, Dh)``
    so the ``G = H // Kh`` query heads sharing a KV head are one MXU
    operand; repeated KV is never materialized;
  - blocks at or beyond a sequence's length are skipped with ``pl.when``
    (no MXU work); unowned table columns point at the trash block 0, so
    the skipped DMA cannot fault. Masking inside the boundary block is
    positional (``kpos < length``), with the optional sliding window
    applied the same way as the slotted path;
  - int8 KV pools dequantize inside the load: per-block-per-head
    symmetric scales ``(L, n_blocks, Kh)`` ride in through SMEM, one
    ``(8, Kh)`` tile of rows per grid step (the least Mosaic takes)
    addressed by the same layer and table lookup, and ``k * scale``
    happens on the VMEM tile — fp KV is never materialized anywhere, and
    the stacked scales reach the kernel in their own shape, with no
    relayout.

Validated against ``kernels.ref.paged_decode_attention_ref`` in
interpret mode (tests sweep block sizes, GQA groups, ragged lengths and
alloc/free block-table permutations).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: pool blocks per int8 scale tile: one grid step DMAs the (8, Kh) rows
#: of scales that hold its block's row
SCALE_ROWS = 8


def _decode_kernel(tables_ref, lengths_ref, layer_ref, *refs, scale: float,
                   bs: int, kh: int, nb: int, rows: int,
                   window: Optional[int], quantized: bool):
    if quantized:
        (q_ref, k_ref, v_ref, ksc_ref, vsc_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    run = j * bs < length                      # block holds visible keys
    if window is not None:
        run = jnp.logical_and(run, (j + 1) * bs - 1 > length - 1 - window)

    row = tables_ref[b, j] % rows             # the block's scale row

    @pl.when(run)
    def _compute():
        for h in range(kh):       # static unroll over the block's KV heads
            q = q_ref[0, h].astype(jnp.float32) * scale          # (G, Dh)
            k = k_ref[0, :, h, :].astype(jnp.float32)            # (bs, Dh)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:
                k = k * ksc_ref[row, h]
                v = v * vsc_ref[row, h]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, bs)
            kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = kpos < length
            if window is not None:
                mask = jnp.logical_and(mask, kpos > length - 1 - window)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h]                                     # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
            acc_scr[h] = acc_scr[h] * alpha + pv
            m_scr[h] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           tables: jax.Array, lengths: jax.Array, *,
                           layer=None,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, Dh); k/v_pool: (L, n_blocks, bs, Kh, Dh) read at
    ``layer`` (an int32 scalar, traced or not), or (n_blocks, bs, Kh, Dh),
    one layer; tables: (B, nb) int32 physical block ids; lengths: (B,)
    int32 KV length per sequence including the current token.
    ``k_scale``/``v_scale`` ((L,) n_blocks, Kh) f32 mark an int8 pool —
    blocks dequantize on their VMEM tile, fp KV is never materialized.
    Returns (B, H, Dh)."""
    if k_pool.ndim == 4:              # one layer: a leading unit axis
        assert layer is None, layer
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    b, h, dh = q.shape
    n_blocks, bs, kh = k_pool.shape[1:4]
    assert h % kh == 0, (h, kh)
    assert (k_scale is None) == (v_scale is None)
    g = h // kh
    nb = tables.shape[1]
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    quantized = k_scale is not None

    rows = min(n_blocks, SCALE_ROWS)
    kernel = functools.partial(_decode_kernel, scale=scale, bs=bs, kh=kh,
                               nb=nb, rows=rows, window=window,
                               quantized=quantized)

    def kv_index(bi, j, tables_ref, lengths_ref, layer_ref):
        return (layer_ref[0], tables_ref[bi, j], 0, 0, 0)

    def scale_index(bi, j, tables_ref, lengths_ref, layer_ref):
        return (layer_ref[0], tables_ref[bi, j] // rows, 0)

    def q_index(bi, j, tables_ref, lengths_ref, layer_ref):
        return (bi, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, kh, g, dh), q_index),
        pl.BlockSpec((None, 1, bs, kh, dh), kv_index),
        pl.BlockSpec((None, 1, bs, kh, dh), kv_index),
    ]
    operands = [q.reshape(b, kh, g, dh), k_pool, v_pool]
    if quantized:
        # a block of 8 rows: a (1, Kh) block would need the scales
        # reshaped to (L, n_blocks, 1, Kh), a relayout of every layer's
        # scales each step; the kernel picks its block's row
        in_specs += [pl.BlockSpec((None, rows, kh), scale_index,
                                  memory_space=pltpu.SMEM)] * 2
        operands += [s.astype(jnp.float32) for s in (k_scale, v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kh, g, dh), q_index),
        scratch_shapes=[
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dh), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    return out.reshape(b, h, dh)
