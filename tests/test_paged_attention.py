"""Paged decode attention: Pallas kernel vs jnp oracle vs dense reference.

Three-way agreement, swept and property-tested:

  * ``kernels.ref.paged_decode_attention_ref`` (the semantics oracle)
    must equal the *dense* ``flash_attention_ref`` on the same history —
    paging is a layout, not a math change;
  * the Pallas kernel (interpret mode on CPU) must match the oracle to
    <= 1e-3 across random slot lengths, block sizes, GQA group counts
    and shuffled block tables (the acceptance bar for the serve decode
    hot path).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import paged_decode_attention

SWEEP = [
    # h, kh, dh, bs, lengths, window, dtype
    (4, 2, 16, 16, (5, 16, 33, 96), None, jnp.float32),
    (4, 4, 32, 8, (1, 7, 8, 64), None, jnp.float32),      # MHA, tiny blocks
    (8, 2, 64, 16, (17, 40), None, jnp.bfloat16),          # wide GQA bf16
    (6, 3, 16, 32, (2, 90, 31), None, jnp.float32),        # odd group count
    (4, 2, 16, 16, (50, 96, 3), 24, jnp.float32),          # windowed
    (2, 1, 64, 16, (33,), 16, jnp.bfloat16),               # windowed bf16
    (8, 2, 64, 16, (17, 40, 1), None, jnp.int8),           # int8 pool
]
#: pool layers of the stacked cases (the decode program's (L, ...) pool)
N_LAYERS = 3


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 \
        else dict(rtol=1e-3, atol=1e-3)


def _paged_setup(lengths, bs, kh, dh, dt, seed=0, max_len=96):
    """Random dense per-sequence KV histories scattered into a pool via
    shuffled block tables (the PagedKVCache layout)."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    max_blocks = -(-max_len // bs)
    n_blocks = 1 + b * max_blocks
    dense_k = rng.normal(size=(b, max_len, kh, dh)).astype(np.float32)
    dense_v = rng.normal(size=(b, max_len, kh, dh)).astype(np.float32)
    k_pool = np.zeros((n_blocks, bs, kh, dh), np.float32)
    v_pool = np.zeros((n_blocks, bs, kh, dh), np.float32)
    tables = np.zeros((b, max_blocks), np.int32)
    free = list(range(1, n_blocks))
    rng.shuffle(free)
    for i, ln in enumerate(lengths):
        for j in range(-(-int(ln) // bs)):
            blk = free.pop()
            tables[i, j] = blk
            k_pool[blk] = dense_k[i, j * bs:(j + 1) * bs]
            v_pool[blk] = dense_v[i, j * bs:(j + 1) * bs]
    to = lambda x: jnp.asarray(x, jnp.float32).astype(dt)
    return (to(dense_k), to(dense_v), to(k_pool), to(v_pool),
            jnp.asarray(tables), jnp.asarray(np.asarray(lengths, np.int32)))


def _quantize(pool):
    """(n_blocks, bs, Kh, Dh) fp -> (int8 codes, (n_blocks, Kh) f32
    per-block-per-head symmetric scales), as the int8 pool holds them."""
    sc = jnp.max(jnp.abs(pool), axis=(1, 3)) / 127.0
    q = jnp.round(pool / jnp.where(sc > 0.0, sc, 1.0)[:, None, :, None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), sc


def _stack(pool, layer, seed):
    """``pool`` as layer ``layer`` of an (N_LAYERS, ...) stack whose other
    layers hold other values."""
    rng = np.random.default_rng(seed)
    layers = [jnp.asarray(rng.normal(size=pool.shape), pool.dtype)
              for _ in range(N_LAYERS)]
    layers[layer] = pool
    return jnp.stack(layers)


def _oracle_cases():
    """Each sweep row on a one-layer pool (ids as the sweep gives them),
    then read as the first and the last layer of a stacked pool."""
    for layer in (None, 0, N_LAYERS - 1):
        for i, row in enumerate(SWEEP):
            h, kh, dh, bs, _lengths, window, dt = row
            at = "" if layer is None else f"-layer{layer}"
            yield pytest.param(*row, layer, id=f"{h}-{kh}-{dh}-{bs}-lengths{i}"
                               f"-{window}-{dt.__name__}{at}")


@pytest.mark.parametrize("h,kh,dh,bs,lengths,window,dt,layer",
                         list(_oracle_cases()))
def test_paged_kernel_matches_oracle(h, kh, dh, bs, lengths, window, dt,
                                     layer):
    """The kernel on a one-layer pool, and on a stacked
    (L, n_blocks, bs, Kh, Dh) pool read at ``layer``: the stacked read
    equals the one-layer kernel on ``pool[layer]`` bit for bit, and both
    match the oracle. int8 pools carry (n_blocks, Kh) scales, stacked
    alike."""
    rng = np.random.default_rng(1)
    b = len(lengths)
    quantized = dt == jnp.int8
    fdt = jnp.bfloat16 if quantized else dt
    q = jnp.asarray(rng.normal(size=(b, h, dh)), jnp.float32).astype(fdt)
    _, _, k_pool, v_pool, tables, lens = _paged_setup(lengths, bs, kh, dh,
                                                      fdt)
    k_sc = v_sc = None
    if quantized:
        (k_pool, k_sc), (v_pool, v_sc) = _quantize(k_pool), _quantize(v_pool)
    want = ref.paged_decode_attention_ref(q, k_pool, v_pool, tables, lens,
                                          window=window, k_scale=k_sc,
                                          v_scale=v_sc)
    got = paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                 window=window, k_scale=k_sc, v_scale=v_sc,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(fdt))
    if layer is None:
        return
    stk = [_stack(x, layer, seed) for seed, x in enumerate(
        (k_pool, v_pool) if k_sc is None else (k_pool, v_pool, k_sc, v_sc))]
    k_sc, v_sc = stk[2:] if quantized else (None, None)
    got_l = paged_decode_attention(q, stk[0], stk[1], tables, lens,
                                   layer=jnp.int32(layer), window=window,
                                   k_scale=k_sc, v_scale=v_sc,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(got_l, np.float32),
                                  np.asarray(got, np.float32))


@pytest.mark.parametrize("h,kh,dh,bs,lengths,window,dt", SWEEP[:4])
def test_paged_oracle_matches_dense_reference(h, kh, dh, bs, lengths,
                                              window, dt):
    """Paging is a layout: the paged oracle over the scattered pool must
    equal dense single-token attention over the contiguous history."""
    rng = np.random.default_rng(2)
    b = len(lengths)
    q = jnp.asarray(rng.normal(size=(b, h, dh)), jnp.float32).astype(dt)
    dense_k, dense_v, k_pool, v_pool, tables, lens = _paged_setup(
        lengths, bs, kh, dh, dt)
    got = ref.paged_decode_attention_ref(q, k_pool, v_pool, tables, lens,
                                         window=window)
    for i, ln in enumerate(lengths):   # per sequence: sq=1 suffix decode
        want = ref.flash_attention_ref(q[i:i + 1, None],
                                       dense_k[i:i + 1, :ln],
                                       dense_v[i:i + 1, :ln],
                                       causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got[i], np.float32),
                                   np.asarray(want[0, 0], np.float32),
                                   **_tol(dt))


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2 ** 16),
    bs=st.sampled_from([8, 16, 32]),
    kh=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 3]),
)
def test_paged_kernel_property(seed, bs, kh, g):
    """Property: kernel == oracle (<=1e-3) for random slot lengths, block
    sizes, GQA group counts, and alloc-order-shuffled block tables."""
    rng = np.random.default_rng(seed)
    b = int(rng.integers(1, 5))
    lengths = rng.integers(1, 97, size=b)
    h, dh = kh * g, 16
    q = jnp.asarray(rng.normal(size=(b, h, dh)), jnp.float32)
    _, _, k_pool, v_pool, tables, lens = _paged_setup(
        lengths, bs, kh, dh, jnp.float32, seed=seed + 1)
    want = ref.paged_decode_attention_ref(q, k_pool, v_pool, tables, lens)
    got = paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_truncated_table_gather_is_exact():
    """Gathering only the first nb table columns (the engine's length
    bucketing) must not change the result while nb*bs covers every live
    length — unowned columns hold the trash block and are masked."""
    lengths = (5, 30)
    _, _, k_pool, v_pool, tables, lens = _paged_setup(lengths, 16, 2, 16,
                                                      jnp.float32)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 4, 16)), jnp.float32)
    full = ref.paged_decode_attention_ref(q, k_pool, v_pool, tables, lens)
    cut = ref.paged_decode_attention_ref(q, k_pool, v_pool, tables[:, :2],
                                         lens)
    np.testing.assert_allclose(np.asarray(cut), np.asarray(full),
                               rtol=1e-6, atol=1e-6)


def test_ops_dispatch_xla_equals_pallas():
    lengths = (9, 48, 96)
    _, _, k_pool, v_pool, tables, lens = _paged_setup(lengths, 16, 2, 16,
                                                      jnp.float32)
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    a = ops.paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                   impl="xla")
    b = ops.paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                   impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-3, atol=1e-3)
