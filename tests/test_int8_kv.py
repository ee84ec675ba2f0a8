"""int8-quantized KV block pool: numerics, invariants, and the serve drill.

Covers the ISSUE 10 quantization tier:

  * symmetric per-(block, KV-head) round-trip error bound
    (|x - deq| <= scale/2: int8 codes are round-to-nearest);
  * the paged prefill kernel over an int8 pool matches the int8 oracle
    (dequantization fused into the KV load, <= 1e-3 interpret mode);
  * ``_quantized_block_write``'s monotone-scale invariant — a decode
    write that fits the block's old range leaves every other code
    bit-unchanged, and the scale never decreases;
  * ``PagedKVCache(kv_dtype="int8")`` structure: scale leaves beside
    the pool, pool bytes <= 0.55x the fp budget, doubled worst-case
    concurrency at that budget;
  * prefix-cache CoW (``copy_blocks``) copies scale leaves with their
    int8 blocks;
  * the fp32-vs-int8 token-stream-quality drill on a real reduced
    model through ``ServeEngine``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.prefill_attention import paged_prefill_attention
from repro.kernels import ref
from repro.models import lm
from repro.models.attention import _quantized_block_write
from repro.serve import cache as cache_lib
from repro.serve.cache import PagedKVCache, copy_blocks
from repro.serve.engine import ServeEngine
from repro.serve.requests import Request
from repro.bench.workloads.serve import stream_agreement

_CONFIG = get_config("llama3.2-3b").reduced(dtype="float32",
                                            param_dtype="float32")


def _quantize_pool(pool):
    """(n_blocks, bs, Kh, Dh) fp -> (int8 pool, (n_blocks, Kh) scales),
    the single-layer form of ``cache._quantize_block``."""
    sc = jnp.max(jnp.abs(pool), axis=(1, 3)) / 127.0
    q = jnp.round(pool / jnp.where(sc > 0.0, sc, 1.0)[:, None, :, None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), sc


def test_quantize_block_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 5, 16, 3, 8)) * 4.0, jnp.float32)
    x = x.at[:, 3].set(0.0)                      # an untouched (zero) block
    q, sc = cache_lib._quantize_block(x)
    assert q.dtype == jnp.int8 and sc.shape == (2, 5, 3)
    deq = q.astype(jnp.float32) * sc[:, :, None, :, None]
    # round-to-nearest: reconstruction is within half a quantization step
    err = np.asarray(jnp.abs(deq - x))
    bound = np.asarray(sc)[:, :, None, :, None] / 2.0 + 1e-7
    assert (err <= bound).all()
    assert np.asarray(deq[:, 3] == 0.0).all()    # zero blocks stay exact


def test_prefill_kernel_int8_matches_int8_ref():
    rng = np.random.default_rng(1)
    b, sq, kh, g, dh, bs, npre, n_blocks = 2, 32, 2, 2, 16, 16, 3, 9
    q = jnp.asarray(rng.normal(size=(b, sq, kh * g, dh)), jnp.float32)
    k_suf = jnp.asarray(rng.normal(size=(b, sq, kh, dh)), jnp.float32)
    v_suf = jnp.asarray(rng.normal(size=(b, sq, kh, dh)), jnp.float32)
    k_pool, k_sc = _quantize_pool(
        jnp.asarray(rng.normal(size=(n_blocks, bs, kh, dh)), jnp.float32))
    v_pool, v_sc = _quantize_pool(
        jnp.asarray(rng.normal(size=(n_blocks, bs, kh, dh)), jnp.float32))
    tables = jnp.asarray(
        rng.permutation(np.arange(1, n_blocks))[:b * npre].reshape(b, npre))
    want = ref.paged_prefill_attention_ref(q, k_suf, v_suf, k_pool, v_pool,
                                           tables, k_scale=k_sc,
                                           v_scale=v_sc)
    got = paged_prefill_attention(q, k_suf, v_suf, k_pool, v_pool, tables,
                                  k_scale=k_sc, v_scale=v_sc, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)
    # and the int8 path tracks the unquantized answer to quantization
    # error, not to garbage
    dense = ref.paged_prefill_attention_ref(
        q, k_suf, v_suf, k_pool.astype(jnp.float32) * k_sc[:, None, :, None],
        v_pool.astype(jnp.float32) * v_sc[:, None, :, None], tables)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=1e-3, atol=1e-3)


def test_quantized_block_write_monotone_scale():
    rng = np.random.default_rng(2)
    n_layers, n, bs, kh, dh = 2, 4, 8, 2, 16
    pool, scale = jax.vmap(_quantize_pool)(jnp.asarray(
        rng.normal(size=(n_layers, n, bs, kh, dh)) * 3.0, jnp.float32))
    layer = jnp.asarray(1, jnp.int32)
    blk = jnp.asarray([1, 2], jnp.int32)
    off = jnp.asarray([5, 2], jnp.int32)

    # a token well inside the blocks' existing range: scale unchanged,
    # every OTHER code in the written blocks bit-identical, and the
    # other layer untouched
    small = jnp.asarray(rng.uniform(-0.5, 0.5, size=(2, kh, dh)), jnp.float32)
    p1, s1 = _quantized_block_write(pool, scale, small, layer, blk, off)
    assert np.array_equal(np.asarray(s1), np.asarray(scale))
    assert np.array_equal(np.asarray(p1[0]), np.asarray(pool[0]))
    for i, (b_, o_) in enumerate(zip([1, 2], [5, 2])):
        old = np.asarray(pool[1, b_])
        new = np.asarray(p1[1, b_])
        mask = np.ones(bs, bool)
        mask[o_] = False
        assert np.array_equal(new[mask], old[mask])
        deq = new[o_] * np.asarray(s1[1, b_])[:, None]
        assert np.abs(deq - np.asarray(small[i])).max() \
            <= np.asarray(s1[1, b_]).max() / 2.0 + 1e-7

    # a token OUTSIDE the range grows the scale; it never shrinks
    big = jnp.full((2, kh, dh), 50.0, jnp.float32)
    p2, s2 = _quantized_block_write(p1, s1, big, layer, blk, off)
    assert (np.asarray(s2) >= np.asarray(s1) - 1e-9).all()
    assert (np.asarray(s2[1, blk]) > np.asarray(s1[1, blk])).all()
    assert np.array_equal(np.asarray(s2[0]), np.asarray(s1[0]))
    deq = np.asarray(p2[1, 1, 5], np.float32) * np.asarray(s2[1, 1])[:, None]
    assert np.abs(deq - 50.0).max() <= np.asarray(s2[1, 1]).max() / 2.0 + 1e-7


def _pool_leaves(caches, suffix=""):
    found = []

    def walk(t):
        if isinstance(t, dict):
            for k, v in t.items():
                if k in ("k" + suffix, "v" + suffix) \
                        and not isinstance(v, dict):
                    found.append(v)
                else:
                    walk(v)
        elif isinstance(t, (list, tuple)):
            for v in t:
                walk(v)

    walk(caches)
    return found


def test_int8_cache_structure_and_capacity():
    kw = dict(n_slots=3, max_len=96, block_size=16, params=None)
    fp = PagedKVCache(_CONFIG, kv_dtype="fp32", **kw)
    i8 = PagedKVCache(_CONFIG, kv_dtype="int8", **kw)
    pools = _pool_leaves(i8.caches)
    scales = _pool_leaves(i8.caches, suffix="_scale")
    assert pools and len(scales) == len(pools)
    for p, s in zip(pools, scales):
        assert p.dtype == jnp.int8
        assert s.dtype == jnp.float32
        assert s.shape == (p.shape[0], p.shape[1], p.shape[3])
    assert not _pool_leaves(fp.caches, suffix="_scale")
    # the ISSUE 10 acceptance bar: int8 pool bytes (codes + scales)
    # <= 0.55x the fp byte budget, which doubles how many worst-case
    # -length requests fit in that budget
    assert i8.pool_bytes_fp == fp.pool_bytes_fp == fp.pool_bytes
    assert i8.pool_bytes <= 0.55 * i8.pool_bytes_fp
    assert i8.max_concurrency >= 2 * fp.max_concurrency


def test_copy_blocks_copies_scale_leaves():
    i8 = PagedKVCache(_CONFIG, n_slots=2, max_len=64, block_size=16,
                      params=None, kv_dtype="int8")

    def stamp(t):
        if not isinstance(t, dict):
            return t
        out = {}
        for k, v in t.items():
            if k in ("k", "v", "k_scale", "v_scale"):
                fill = 7 if k in ("k", "v") else 0.25
                out[k] = v.at[:, 1].set(jnp.asarray(fill, v.dtype))
            else:
                out[k] = stamp(v)
        return out

    caches = stamp(i8.caches)
    out = copy_blocks(caches, jnp.asarray([1]), jnp.asarray([3]))

    def check(t):
        if not isinstance(t, dict):
            return
        for k, v in t.items():
            if k in ("k", "v", "k_scale", "v_scale"):
                np.testing.assert_array_equal(np.asarray(v[:, 3]),
                                              np.asarray(v[:, 1]))
            else:
                check(v)

    check(out)


@pytest.mark.parametrize("sched", ["phased"])
def test_engine_int8_stream_quality_drill(sched):
    """fp32-vs-int8 KV on a real (reduced, float32) model: the int8
    engine must complete every request and its greedy token streams
    must agree with the fp32 engine's to a long common prefix — the
    same statistic the serve workload compare-gates
    (``kv_stream_prefix_agreement``)."""
    params = lm.init(jax.random.key(0), _CONFIG)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, _CONFIG.vocab, p, np.int32),
                    max_new_tokens=b, arrival_s=0.0)
            for i, (p, b) in enumerate([(5, 24), (20, 16), (40, 12)])]

    def run(kv_dtype):
        eng = ServeEngine(_CONFIG, params, n_slots=3, max_len=96,
                          cache="paged", block_size=16, decode_window=8,
                          kv_dtype=kv_dtype)
        out = eng.serve([Request(r.rid, r.prompt, r.max_new_tokens,
                                 arrival_s=r.arrival_s) for r in reqs],
                        sched=sched)
        return {r.rid: list(r.tokens) for r in out.results}

    fp_streams = run("fp32")
    i8_streams = run("int8")
    assert set(i8_streams) == set(fp_streams)
    assert all(len(t) > 0 for t in i8_streams.values())
    agree = stream_agreement(fp_streams, i8_streams)
    # quantization noise may fork a greedy stream eventually; it must
    # not fork it immediately (smoke cell measured 0.85)
    assert agree >= 0.6, f"stream agreement {agree:.3f} < 0.6"
