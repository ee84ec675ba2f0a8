"""jit'd dispatch wrappers for the Pallas kernels.

``impl="pallas"`` targets TPU (interpret mode on CPU, in tests);
``impl="xla"`` routes to the pure-jnp reference path. The serve engine
picks between them from the platform (``serve.engine.paged_kernel``).

Selection map (who runs what, where):

  flash_attention        prefill/train attention; ``impl="pallas"`` on
                         TPU, ``impl="xla"`` (ref) on CPU.
  paged_decode_attention the serve decode hot path over a paged KV pool
                         (``serve.cache.PagedKVCache`` block tables),
                         read at one layer of the stacked pool that
                         the decode step updates in place.
                         ``impl="xla"`` gathers the table into a dense
                         view (the CPU path); ``impl="pallas"`` walks
                         the table with scalar-prefetch DMA — the TPU
                         path, validated on CPU via ``interpret=True``.
  paged_prefill_attention the serve *prefill* hot path: a chunk of Q
                         positions vs [the slot's paged prefix blocks ++
                         the chunk's own suffix KV]. Same xla/pallas
                         split; the xla path is bit-compatible with the
                         engine's dense phased prefill (the serve stream
                         contract).
  rmsnorm                elementwise; same pallas/xla split.

Both paged ops accept optional ``k_scale``/``v_scale`` (n_blocks, Kh)
f32 marking an int8-quantized pool; dequant happens inside the kernel's
KV load (pallas) or right after the gather (xla ref).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import (
    paged_decode_attention as _paged_decode_pallas,
)
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.prefill_attention import (
    paged_prefill_attention as _paged_prefill_pallas,
)
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_pallas


@partial(jax.jit, static_argnames=("causal", "window", "impl", "interpret",
                                   "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, impl: str = "pallas",
                    interpret: bool = False, block_q: int = 128,
                    block_k: int = 128):
    """q: (B, Sq, H, Dh); k/v: (B, Skv, Kh, Dh) — model layout (seq-major).

    Transposed internally to the kernel's (B, H, S, Dh) layout.
    """
    if impl == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash_pallas(qt, kt, vt, causal=causal, window=window,
                        block_q=block_q, block_k=block_k,
                        interpret=interpret)
    return out.transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("window", "impl", "interpret", "scale"))
def paged_decode_attention(q, k_pool, v_pool, tables, lengths, *,
                           layer=None, window: Optional[int] = None,
                           impl: str = "pallas", interpret: bool = False,
                           k_scale=None, v_scale=None,
                           scale: Optional[float] = None):
    """Single-token GQA decode over a paged KV pool.

    q: (B, H, Dh); k/v_pool: (L, n_blocks, bs, Kh, Dh) read at the int32
    scalar ``layer``, or (n_blocks, bs, Kh, Dh) with ``layer`` None;
    tables: (B, nb) int32 physical block ids (position order, trash
    block 0 for unowned columns); lengths: (B,) int32 KV length incl.
    the current token. k/v_scale: optional ((L,) n_blocks, Kh) f32
    int8-pool scales. ``scale`` multiplies the scores (None: 1/sqrt(Dh)).
    """
    if impl == "xla":
        if layer is not None:
            k_pool, v_pool = k_pool[layer], v_pool[layer]
            if k_scale is not None:
                k_scale, v_scale = k_scale[layer], v_scale[layer]
        return ref.paged_decode_attention_ref(q, k_pool, v_pool, tables,
                                              lengths, window=window,
                                              scale=scale, k_scale=k_scale,
                                              v_scale=v_scale)
    return _paged_decode_pallas(q, k_pool, v_pool, tables, lengths,
                                layer=layer, window=window, scale=scale,
                                k_scale=k_scale, v_scale=v_scale,
                                interpret=interpret)


@partial(jax.jit, static_argnames=("window", "impl", "interpret",
                                   "block_q", "block_k", "scale"))
def paged_prefill_attention(q, k_suffix, v_suffix, k_pool, v_pool, tables, *,
                            window: Optional[int] = None,
                            impl: str = "pallas", interpret: bool = False,
                            block_q: int = 128, block_k: int = 128,
                            k_scale=None, v_scale=None,
                            scale: Optional[float] = None):
    """Chunk-of-queries causal GQA attention over [paged prefix ++ own
    suffix KV].

    q: (B, Sq, H, Dh); k/v_suffix: (B, Sq, Kh, Dh); k/v_pool:
    (n_blocks, bs, Kh, Dh); tables: (B, npre) int32 prefix block ids in
    position order (queries sit at global positions npre*bs + i).
    k/v_scale: optional (n_blocks, Kh) f32 int8-pool scales. ``scale``
    multiplies the scores (None: 1/sqrt(Dh)).
    """
    if impl == "xla":
        return ref.paged_prefill_attention_ref(q, k_suffix, v_suffix,
                                               k_pool, v_pool, tables,
                                               window=window, scale=scale,
                                               k_scale=k_scale,
                                               v_scale=v_scale)
    return _paged_prefill_pallas(q, k_suffix, v_suffix, k_pool, v_pool,
                                 tables, window=window, scale=scale,
                                 k_scale=k_scale,
                                 v_scale=v_scale, block_q=block_q,
                                 block_k=block_k, interpret=interpret)


@partial(jax.jit, static_argnames=("impl", "interpret", "eps"))
def rmsnorm(x, scale, *, eps: float = 1e-5, impl: str = "pallas",
            interpret: bool = False):
    if impl == "xla":
        return ref.rmsnorm_ref(x, scale, eps=eps)
    return _rmsnorm_pallas(x, scale, eps=eps, interpret=interpret)
