"""Paged prefill flash attention: Pallas kernel vs oracle vs dense.

Three-way agreement plus the engine-dispatch contract:

  * ``kernels.ref.paged_prefill_attention_ref`` (the semantics oracle)
    must equal dense full-sequence attention on the concatenated
    [prefix ++ suffix] history, sliced to the suffix positions — paged
    prefill is a layout, not a math change, and suffix attention is
    independent of the prefix rows' own queries;
  * the Pallas kernel (interpret mode on CPU) must match the oracle to
    <= 1e-3 across shapes, block sizes, GQA group counts, prefix depths
    (pos_offset), shuffled block tables, windows and dtypes (the
    ISSUE 10 acceptance bar for the serve prefill hot path);
  * ``ServeEngine``'s chunked prefill and prefix-cache suffix prefill
    must actually dispatch ``ops.paged_prefill_attention`` — no dense
    prefix-KV gather on the paged path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.prefill_attention import paged_prefill_attention
from repro.models import lm
from repro.serve.engine import ServeEngine
from repro.serve.requests import Request

SWEEP = [
    # g, kh, dh, bs, sq, npre, window, dtype
    (2, 2, 16, 16, 32, 3, None, jnp.float32),
    (1, 4, 32, 8, 24, 2, None, jnp.float32),     # MHA, tiny blocks
    (4, 2, 64, 16, 16, 1, None, jnp.bfloat16),   # wide GQA bf16
    (3, 2, 16, 16, 48, 2, None, jnp.float32),    # odd group count
    (2, 2, 16, 16, 32, 4, 40, jnp.float32),      # window crosses prefix
    (2, 1, 64, 16, 16, 2, 16, jnp.bfloat16),     # window == block bf16
    (2, 2, 16, 16, 144, 3, None, jnp.float32),   # Sq % 128 != 0 tile walk
]


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 \
        else dict(rtol=1e-3, atol=1e-3)


def _prefill_setup(b, sq, npre, bs, kh, dh, g, dt, seed=0):
    """Random dense prefix histories scattered into a pool via shuffled
    block tables + a fresh suffix chunk (the chunked / prefix-cached
    serve prefill layout)."""
    rng = np.random.default_rng(seed)
    h = kh * g
    n_blocks = 1 + b * npre + 2          # trash + prefixes + idle spares
    dense_k = rng.normal(size=(b, npre * bs, kh, dh)).astype(np.float32)
    dense_v = rng.normal(size=(b, npre * bs, kh, dh)).astype(np.float32)
    k_pool = rng.normal(size=(n_blocks, bs, kh, dh)).astype(np.float32)
    v_pool = rng.normal(size=(n_blocks, bs, kh, dh)).astype(np.float32)
    tables = np.zeros((b, npre), np.int32)
    free = list(range(1, n_blocks))
    rng.shuffle(free)
    for i in range(b):
        for j in range(npre):
            blk = free.pop()
            tables[i, j] = blk
            k_pool[blk] = dense_k[i, j * bs:(j + 1) * bs]
            v_pool[blk] = dense_v[i, j * bs:(j + 1) * bs]
    q = rng.normal(size=(b, sq, h, dh)).astype(np.float32)
    k_suf = rng.normal(size=(b, sq, kh, dh)).astype(np.float32)
    v_suf = rng.normal(size=(b, sq, kh, dh)).astype(np.float32)
    to = lambda x: jnp.asarray(x, jnp.float32).astype(dt)
    return (to(q), to(k_suf), to(v_suf), to(k_pool), to(v_pool),
            jnp.asarray(tables), to(dense_k), to(dense_v))


@pytest.mark.parametrize("g,kh,dh,bs,sq,npre,window,dt", SWEEP)
def test_prefill_kernel_matches_oracle(g, kh, dh, bs, sq, npre, window, dt):
    (q, k_suf, v_suf, k_pool, v_pool, tables,
     _, _) = _prefill_setup(2, sq, npre, bs, kh, dh, g, dt)
    want = ref.paged_prefill_attention_ref(q, k_suf, v_suf, k_pool, v_pool,
                                           tables, window=window)
    got = paged_prefill_attention(q, k_suf, v_suf, k_pool, v_pool, tables,
                                  window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))


@pytest.mark.parametrize("g,kh,dh,bs,sq,npre,window,dt", SWEEP[:5])
def test_prefill_oracle_matches_dense_full_sequence(g, kh, dh, bs, sq, npre,
                                                    window, dt):
    """Paging is a layout: the paged oracle over the scattered pool must
    equal dense full-sequence causal attention over the contiguous
    [prefix ++ suffix], read at the suffix positions. The prefix rows'
    queries are free variables (suffix attention never sees them)."""
    (q, k_suf, v_suf, k_pool, v_pool, tables,
     dense_k, dense_v) = _prefill_setup(2, sq, npre, bs, kh, dh, g, dt,
                                        seed=3)
    got = ref.paged_prefill_attention_ref(q, k_suf, v_suf, k_pool, v_pool,
                                          tables, window=window)
    rng = np.random.default_rng(4)
    q_pre = jnp.asarray(rng.normal(size=(2, npre * bs, kh * g, dh)),
                        jnp.float32).astype(dt)
    q_full = jnp.concatenate([q_pre, q], axis=1)
    k_full = jnp.concatenate([dense_k, k_suf], axis=1)
    v_full = jnp.concatenate([dense_v, v_suf], axis=1)
    want = ref.flash_attention_ref(q_full, k_full, v_full, causal=True,
                                   window=window)[:, npre * bs:]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2 ** 16),
    bs=st.sampled_from([8, 16]),
    kh=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 2, 3]),
    npre=st.integers(1, 4),
)
def test_prefill_kernel_property(seed, bs, kh, g, npre):
    """Property: kernel == oracle (<=1e-3) for random batch/suffix
    shapes (including Sq the tile walk-down must split unevenly),
    prefix depths, GQA groups and shuffled tables, with and without a
    sliding window."""
    rng = np.random.default_rng(seed)
    b = int(rng.integers(1, 4))
    sq = int(rng.integers(1, 49))
    window = None if rng.random() < 0.5 \
        else int(rng.integers(bs, npre * bs + sq))
    (q, k_suf, v_suf, k_pool, v_pool, tables,
     _, _) = _prefill_setup(b, sq, npre, bs, kh, 16, g, jnp.float32,
                            seed=seed + 1)
    want = ref.paged_prefill_attention_ref(q, k_suf, v_suf, k_pool, v_pool,
                                           tables, window=window)
    got = paged_prefill_attention(q, k_suf, v_suf, k_pool, v_pool, tables,
                                  window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_ops_dispatch_xla_equals_pallas():
    (q, k_suf, v_suf, k_pool, v_pool, tables,
     _, _) = _prefill_setup(2, 32, 3, 16, 2, 16, 2, jnp.float32, seed=7)
    a = ops.paged_prefill_attention(q, k_suf, v_suf, k_pool, v_pool, tables,
                                    impl="xla")
    b = ops.paged_prefill_attention(q, k_suf, v_suf, k_pool, v_pool, tables,
                                    impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Engine dispatch: the paged path never gathers dense prefix KV
# ---------------------------------------------------------------------------


def test_engine_prefill_paths_dispatch_paged_kernel(monkeypatch):
    """Both chunked prefill (non-first chunks) and prefix-cache suffix
    prefill must route through ``ops.paged_prefill_attention``. The spy
    wraps the op BEFORE the engines compile, so every traced prefill
    program records its dispatch; streams must stay bit-identical to
    the phased/cold runs made without the spy."""
    c = get_config("llama3.2-3b").reduced(dtype="float32",
                                          param_dtype="float32")
    params = lm.init(jax.random.key(0), c)
    rng = np.random.default_rng(21)
    shared = rng.integers(0, c.vocab, 32, np.int32)
    tails = rng.integers(0, c.vocab, (6, 6), np.int32)
    # more requests than slots: the first wave registers the shared
    # prefix, the second wave's admissions hit it (suffix prefill path)
    prefix_reqs = [Request(rid=i, prompt=np.concatenate([shared, tails[i]]),
                           max_new_tokens=8) for i in range(6)]
    long_reqs = [Request(rid=i, prompt=rng.integers(0, c.vocab, p, np.int32),
                         max_new_tokens=6)
                 for i, p in enumerate([48, 64, 40])]

    def make(**kw):
        return ServeEngine(c, params, n_slots=3, max_len=96, cache="paged",
                           block_size=16, decode_window=8, **kw)

    base_prefix = make(prefix_cache=True).serve(list(prefix_reqs),
                                                policy="continuous")
    base_chunk = make().serve(list(long_reqs), policy="continuous",
                              sched="chunked")

    calls = []
    real = ops.paged_prefill_attention

    def spy(*args, **kw):
        calls.append(kw.get("impl", "xla"))
        return real(*args, **kw)

    monkeypatch.setattr(ops, "paged_prefill_attention", spy)

    eng = make(prefix_cache=True)
    out = eng.serve(list(prefix_reqs), policy="continuous")
    assert eng.prefix_stats["hit_requests"] > 0
    n_prefix = len(calls)
    assert n_prefix > 0, "prefix-cache suffix prefill bypassed the kernel"

    eng2 = make()
    out2 = eng2.serve(list(long_reqs), policy="continuous", sched="chunked")
    assert len(calls) > n_prefix, "chunked prefill bypassed the kernel"

    # dispatching through the paged kernel is invisible in the streams
    assert {r.rid: r.tokens for r in out.results} \
        == {r.rid: r.tokens for r in base_prefix.results}
    assert {r.rid: r.tokens for r in out2.results} \
        == {r.rid: r.tokens for r in base_chunk.results}


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_engine_serves_through_pallas_kernels_in_interpret_mode(
        monkeypatch, kv_dtype):
    """The TPU serve path on CPU: with the engine's kernel choice steered
    to the Pallas kernels (interpret mode), chunked serving dispatches
    both paged kernels and yields the same greedy streams as the XLA
    reference path the engine picks on CPU."""
    from repro.serve import engine as engine_mod

    c = get_config("llama3.2-3b").reduced(dtype="float32",
                                          param_dtype="float32")
    params = lm.init(jax.random.key(0), c)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, c.vocab, p, np.int32),
                    max_new_tokens=6)
            for i, p in enumerate([48, 64, 40])]

    def serve():
        eng = ServeEngine(c, params, n_slots=3, max_len=96, cache="paged",
                          block_size=16, kv_dtype=kv_dtype)
        return eng, eng.serve(list(reqs), policy="continuous",
                              sched="chunked")

    ref_eng, ref_out = serve()
    assert ref_eng.paged_impl == "xla"       # what CPU picks by itself

    calls = []

    def spy(real):
        def wrapped(*args, **kw):
            calls.append((real.__name__, kw["impl"], kw["interpret"]))
            return real(*args, **kw)
        return wrapped

    monkeypatch.setattr(engine_mod, "paged_kernel", lambda: ("pallas", True))
    monkeypatch.setattr(ops, "paged_decode_attention",
                        spy(ops.paged_decode_attention))
    monkeypatch.setattr(ops, "paged_prefill_attention",
                        spy(ops.paged_prefill_attention))
    _eng, out = serve()
    assert {name for name, impl, interp in calls
            if impl == "pallas" and interp} == {
        "paged_decode_attention", "paged_prefill_attention"}
    assert all(len(r.tokens) == 6 for r in out.results)
    assert {r.rid: r.tokens for r in out.results} \
        == {r.rid: r.tokens for r in ref_out.results}
