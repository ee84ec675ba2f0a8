"""Paged KV cache + serve engine equivalence.

The contract: the paged cache, the batched prefill, and the fused decode
window are *performance* features — they must be invisible in the token
streams. Everything here runs a small float32 model (bf16 argmax ties
would flake) and asserts exact equality between

  slotted/legacy-window == slotted/fused == paged/fused == paged/legacy

plus allocator invariants (no block aliasing across alloc/free/refill
sequences, OOM signalling, trash-block discipline) and the paged insert.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.configs import get_config
from repro.models import lm
from repro.serve.cache import (
    CacheOOM, PagedKVCache, grow_caches, insert_paged_rows, insert_rows,
    slotted_cache,
)
from repro.serve.engine import ServeEngine
from repro.serve.requests import Request, poisson_requests

N_SLOTS, MAX_LEN, BS, PROMPT = 3, 64, 16, 5

_CONFIG = get_config("llama3.2-3b").reduced(dtype="float32",
                                            param_dtype="float32")


@pytest.fixture(scope="module")
def setup():
    return _CONFIG, lm.init(jax.random.key(0), _CONFIG)


# ---------------------------------------------------------------------------
# Allocator invariants (host-side; abstract params — no real weights)
# ---------------------------------------------------------------------------


def _blank_cache(**kw):
    return PagedKVCache(_CONFIG, N_SLOTS, MAX_LEN, None, block_size=BS, **kw)


def test_paged_pool_shapes_and_trash_block():
    cache = _blank_cache()
    assert cache.max_blocks == MAX_LEN // BS
    assert cache.n_blocks == 1 + N_SLOTS * cache.max_blocks
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache.caches)[0]:
        key = getattr(path[-1], "key", None)
        if key in ("k", "v"):
            assert leaf.shape[1:3] == (cache.n_blocks, BS)
        else:
            assert leaf.shape[1] == N_SLOTS   # state leaves stay slotted
    assert cache.free_blocks == cache.n_blocks - 1   # block 0 reserved
    assert np.all(cache.tables_np == 0)              # all columns -> trash


def test_ensure_allocates_and_frees_return():
    cache = _blank_cache()
    cache.ensure(1, PROMPT)                  # 5 tokens -> 1 block
    assert cache.owned(1) == 1
    cache.ensure(1, BS + 1)                  # crosses a block boundary
    assert cache.owned(1) == 2
    cache.ensure(1, BS + 1)                  # idempotent
    assert cache.owned(1) == 2
    ids = cache.block_ids(1, BS + 1)
    assert len(set(ids.tolist())) == 2 and 0 not in ids
    free_before = cache.free_blocks
    cache.free(1)
    assert cache.owned(1) == 0
    assert cache.free_blocks == free_before + 2
    assert np.all(cache.tables_np[1] == 0)   # row reverted to trash


def test_pool_oom_raises():
    cache = PagedKVCache(_CONFIG, N_SLOTS, MAX_LEN, None, block_size=BS,
                         n_blocks=1 + MAX_LEN // BS)   # one full slot only
    cache.ensure(0, MAX_LEN)
    with pytest.raises(CacheOOM):
        cache.ensure(1, 1)
    cache.free(0)
    cache.ensure(1, 1)                       # freed blocks are reusable


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_alloc_free_never_aliases_blocks(seed):
    """Property: across random alloc/free/refill sequences, no physical
    block (except trash) is ever owned by two slots, and the table rows
    mirror the owned lists exactly."""
    cache = _blank_cache()
    rng = np.random.default_rng(seed)
    lengths = [0] * N_SLOTS
    for _ in range(50):
        slot = int(rng.integers(N_SLOTS))
        if rng.random() < 0.3 and lengths[slot]:
            cache.free(slot)
            lengths[slot] = 0
        else:
            lengths[slot] = min(lengths[slot] + int(rng.integers(1, 20)),
                                MAX_LEN)
            cache.ensure(slot, lengths[slot])
        owned = [cache.tables_np[s, :cache.owned(s)].tolist()
                 for s in range(N_SLOTS)]
        flat = [b for row in owned for b in row]
        assert 0 not in flat                      # trash is never owned
        assert len(flat) == len(set(flat))        # no aliasing
        for s in range(N_SLOTS):                  # unowned columns -> trash
            assert np.all(cache.tables_np[s, cache.owned(s):] == 0)
        assert len(flat) + cache.free_blocks == cache.n_blocks - 1


# ---------------------------------------------------------------------------
# Insert + decode equivalence (real model, fp32)
# ---------------------------------------------------------------------------


def test_paged_insert_then_decode_matches_slotted(setup):
    """One prefilled prompt inserted into both layouts, then a decode
    step: logits must agree (same math, different addressing)."""
    c, params = setup
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, c.vocab, (1, BS)))  # one block
    logits_p, row, _ = lm.prefill(c, params, tokens)
    rows = jax.tree.map(lambda l: l, row)

    slot, plen = 1, BS
    slotted = slotted_cache(c, N_SLOTS, MAX_LEN, params)
    slotted = insert_rows(slotted, rows, jnp.asarray([slot], jnp.int32))

    paged = PagedKVCache(c, N_SLOTS, MAX_LEN, params, block_size=BS)
    paged.ensure(slot, plen)
    blocks = paged.block_ids(slot, plen)[None]
    caches_p = insert_paged_rows(paged.caches, rows, jnp.asarray(blocks),
                                 jnp.asarray([slot], jnp.int32),
                                 block_size=BS)
    paged.ensure(slot, plen + 1)   # the engine grows before each decode

    tok = jnp.asarray(np.full((N_SLOTS, 1),
                              int(jnp.argmax(logits_p[0, -1]))), jnp.int32)
    pos = np.full((N_SLOTS,), MAX_LEN - 1, np.int32)
    pos[slot] = plen
    out_s, _ = lm.decode_step(c, params, tok, slotted, jnp.asarray(pos))
    out_p, _ = lm.decode_step(c, params, tok, caches_p, jnp.asarray(pos),
                              block_tables=paged.device_tables(),
                              n_kv_blocks=2)
    np.testing.assert_allclose(np.asarray(out_p[slot]),
                               np.asarray(out_s[slot]),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_writes_only_the_token_rows(setup):
    """One paged decode step over a random stacked pool: each layer's
    pool comes back equal to the input but for the 16 (slot, layer)
    token rows at ``[blk, off]`` (8 slots x 2 layers), each holding the
    token's K/V as the slotted decode writes it; every other block is
    bit-unchanged, and the idle slots' rows land in the trash block 0.
    The live slots' logits equal the slotted decode's over the same
    history."""
    c, params = setup
    n_slots, live = 8, (7, 16, 33, 1, 50, 20)         # 2 idle slots
    mb = MAX_LEN // BS
    n_blocks = 1 + n_slots * mb
    rng = np.random.default_rng(11)
    paged = PagedKVCache(c, n_slots, MAX_LEN, params, block_size=BS)
    assert paged.caches["slot0"]["k"].shape[:2] == (c.n_layers, n_blocks)
    pool = jax.tree.map(lambda l: jnp.asarray(rng.normal(size=l.shape),
                                              l.dtype), paged.caches)
    tables = np.zeros((n_slots, mb), np.int32)
    free = rng.permutation(np.arange(1, n_blocks))
    pos = np.zeros(n_slots, np.int32)
    for s, ln in enumerate(live):           # ln tokens held, one more due
        n = ln // BS + 1
        tables[s, :n], free = free[:n], free[n:]
        pos[s] = ln
    pos[6], pos[7] = MAX_LEN - 1, MAX_LEN - 2  # idle: trash column
    tok = jnp.asarray(rng.integers(0, c.vocab, (n_slots, 1)), jnp.int32)
    logits, out = lm.decode_step(c, params, tok, pool, jnp.asarray(pos),
                                 block_tables=jnp.asarray(tables),
                                 n_kv_blocks=mb)

    # the slotted cache holding each live slot's history from the pool
    dense = {"slot0": {kv: jnp.asarray(np.asarray(pool["slot0"][kv])[
        :, tables].reshape(c.n_layers, n_slots, MAX_LEN, c.n_kv_heads,
                           c.d_head)) for kv in ("k", "v")}}
    want_logits, want = lm.decode_step(c, params, tok, dense,
                                       jnp.asarray(pos))
    rows = list(range(len(live)))
    np.testing.assert_allclose(np.asarray(logits)[rows],
                               np.asarray(want_logits)[rows],
                               rtol=2e-5, atol=2e-5)
    written = 0
    for kv in ("k", "v"):
        before = np.asarray(pool["slot0"][kv])
        after = np.asarray(out["slot0"][kv])
        expect = before.copy()
        for layer in range(c.n_layers):
            for s in range(n_slots):
                blk, off = tables[s, pos[s] // BS], pos[s] % BS
                assert (blk == 0) == (s >= len(live))
                row = after[layer, blk, off]
                assert not np.array_equal(row, before[layer, blk, off])
                if blk:
                    np.testing.assert_allclose(
                        row, np.asarray(want["slot0"][kv])[layer, s, pos[s]],
                        rtol=2e-5, atol=2e-5)
                expect[layer, blk, off] = row
                written += 1
        np.testing.assert_array_equal(after, expect)
    assert written == 2 * 16


# ---------------------------------------------------------------------------
# Engine-level: layouts and fused windows are invisible in token streams
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(setup):
    c, params = setup
    reqs = poisson_requests(12, 400.0, c.vocab, prompt_len=PROMPT, seed=3,
                            short=(2, 8), long=(30, 50))
    out = {}
    for kind in ("slotted", "paged"):
        for window in (1, 8):
            eng = ServeEngine(c, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                              cache=kind, block_size=BS,
                              decode_window=window)
            out[(kind, window)] = eng.serve(reqs, policy="continuous"), eng
    return out


def test_layouts_and_fusion_produce_identical_tokens(served):
    ref = served[("slotted", 1)][0].by_rid()
    for key, (run, _) in served.items():
        got = run.by_rid()
        for rid in ref:
            assert got[rid].tokens == ref[rid].tokens, (key, rid)
            assert got[rid].finish_reason == ref[rid].finish_reason


def test_fused_runs_record_exact_token_accounting(served):
    """Fused decode windows must credit each rid once per micro-step."""
    run, _ = served[("paged", 8)]
    for rec in run.steps:
        if rec.kind == "decode":
            assert rec.n_tokens == len(rec.rids) == rec.n_steps * (
                len(set(rec.rids)))
    total_gen = sum(r.n_tokens for r in run.results)
    credited = sum(s.n_tokens for s in run.steps)
    assert credited == total_gen
    assert 0.0 < run.summary.mean_occupancy <= 1.0


def test_paged_engine_frees_all_blocks_after_drain(served):
    _, eng = served[("paged", 8)]
    pool = eng._paged
    assert pool.free_blocks == pool.n_blocks - 1
    assert np.all(pool.tables_np == 0)


def test_eos_frees_paged_blocks_early(setup):
    """EOS early-exit must release a slot's blocks immediately (and the
    scheduler falls back to the per-token window when EOS is possible)."""
    c, params = setup
    eos = 7
    prompts = np.zeros((2, PROMPT), np.int32)
    reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=40,
                    arrival_s=0.0, eos_id=eos) for i in range(2)]
    eng = ServeEngine(c, params, n_slots=2, max_len=MAX_LEN, cache="paged",
                      block_size=BS, decode_window=8)
    out = eng.serve(reqs, policy="continuous")
    assert eng._paged.free_blocks == eng._paged.n_blocks - 1
    for rec in out.steps:   # EOS-capable slots force single-step windows
        if rec.kind == "decode":
            assert rec.n_steps == 1
    for r in out.results:
        if r.finish_reason == "eos":
            assert r.tokens[-1] == eos


def test_ssm_family_batched_prefill_keeps_exact_state():
    """Stacks with mamba layers must prefill at exact prompt length:
    right-padding would run the SSD recurrence/conv tail through pad
    tokens and corrupt decode state (masking protects attention only).
    The engine's serve tokens must match a manual unpadded
    prefill+decode chain."""
    c = get_config("mamba2-1.3b").reduced(dtype="float32",
                                          param_dtype="float32")
    params = lm.init(jax.random.key(1), c)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, c.vocab, PROMPT).astype(np.int32)
    budget = 6

    logits, caches, _ = lm.prefill(c, params, jnp.asarray(prompt[None]))
    caches = grow_caches(caches, 32)
    want = [int(jnp.argmax(logits[0, -1]))]
    pos = PROMPT
    for _ in range(budget - 1):
        tok = jnp.asarray([[want[-1]]], jnp.int32)
        logits, caches = lm.decode_step(c, params, tok, caches,
                                        jnp.int32(pos))
        want.append(int(jnp.argmax(logits[0, -1])))
        pos += 1

    eng = ServeEngine(c, params, n_slots=2, max_len=32, cache="slotted",
                      decode_window=4)
    out = eng.serve([Request(rid=0, prompt=prompt, max_new_tokens=budget)],
                    policy="continuous")
    assert out.by_rid()[0].tokens == want


def test_paged_matches_slotted_for_ssm_hybrid_stack():
    """The trickiest layout interaction: ssm/hybrid stacks prefill at
    EXACT prompt length while attention KV pages into the shared pool —
    per-slot SSM/conv state rides beside (L, n_blocks, bs, ...) leaves.
    Token streams must be identical to the slotted reference."""
    c = get_config("mamba2-1.3b").reduced(dtype="float32",
                                          param_dtype="float32")
    params = lm.init(jax.random.key(1), c)
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, c.vocab, PROMPT).astype(np.int32),
                    max_new_tokens=6, arrival_s=0.0) for i in range(3)]

    def run(cache):
        eng = ServeEngine(c, params, n_slots=2, max_len=32, cache=cache,
                          block_size=16, decode_window=4)
        out = eng.serve(list(reqs), policy="continuous")
        return {r.rid: r.tokens for r in out.results}

    assert run("paged") == run("slotted")


def test_oversubscribed_pool_serves_when_load_fits(setup):
    """The HBM lever: a pool with fewer blocks than n_slots*max_blocks
    still serves short requests (they only touch what they own)."""
    c, params = setup
    n_blocks = 1 + (MAX_LEN // BS) + 2      # one full slot + 2 spare
    eng = ServeEngine(c, params, n_slots=2, max_len=MAX_LEN, cache="paged",
                      block_size=BS, n_blocks=n_blocks, decode_window=4)
    reqs = [Request(rid=i, prompt=np.zeros(PROMPT, np.int32),
                    max_new_tokens=8, arrival_s=0.0) for i in range(4)]
    out = eng.serve(reqs, policy="continuous")
    assert all(r.finish_reason == "length" for r in out.results)
    assert eng._paged.free_blocks == n_blocks - 1


def test_oversubscribed_pool_defers_admission_instead_of_oom(setup):
    """Admission control: concurrent worst-case demand that OUTGROWS the
    pool must defer admissions (requests wait in the queue for finishing
    slots to free blocks) and serve everyone — the pre-admission-control
    engine died on CacheOOM here."""
    c, params = setup
    # pool holds exactly one full slot + trash; three slots' worth of
    # near-max-budget requests is 3x the pool
    n_blocks = 1 + MAX_LEN // BS
    eng = ServeEngine(c, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                      cache="paged", block_size=BS, n_blocks=n_blocks,
                      decode_window=4)
    budget = MAX_LEN - PROMPT
    reqs = [Request(rid=i, prompt=np.zeros(PROMPT, np.int32),
                    max_new_tokens=budget, arrival_s=0.0)
            for i in range(3)]
    out = eng.serve(reqs, policy="continuous")
    assert sorted(r.rid for r in out.results) == [0, 1, 2]
    assert all(r.finish_reason == "length" for r in out.results)
    assert all(len(r.tokens) == budget for r in out.results)
    # FIFO preserved under deferral: rid 0 finishes no later than rid 2
    by = out.by_rid()
    assert by[0].finish_s <= by[2].finish_s
    # every block returned; reservation ledger empty
    assert eng._paged.free_blocks == n_blocks - 1
    assert eng._slot_cap == {}


def test_deferred_admission_token_streams_match_roomy_pool(setup):
    """Deferral is scheduling only: the tokens a request generates are
    identical to a run where the pool never had to defer."""
    c, params = setup
    rng = np.random.default_rng(9)
    budget = MAX_LEN - PROMPT
    prompts = [rng.integers(0, c.vocab, PROMPT).astype(np.int32)
               for _ in range(3)]

    def run(n_blocks):
        eng = ServeEngine(c, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                          cache="paged", block_size=BS, n_blocks=n_blocks,
                          decode_window=4)
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=budget,
                        arrival_s=0.0) for i in range(3)]
        return {r.rid: r.tokens for r in
                eng.serve(reqs, policy="continuous").results}

    tight = run(1 + MAX_LEN // BS)          # one slot at a time
    roomy = run(None)                       # full worst-case reservation
    assert tight == roomy


def test_deferred_head_does_not_block_fused_windows(setup):
    """The deferral-fusion bug: a headroom-deferred queue head used to
    count as 'free slot + pending work', dropping the whole pool to
    per-token cadence (plus re-admit/unadmit churn every loop) for as
    long as the deferral lasted. A blocked head cannot admit until a
    finish frees blocks, and finishes land only on window edges — so
    the solo resident must still take fused windows."""
    c, params = setup
    n_blocks = 1 + MAX_LEN // BS             # one full slot at a time
    eng = ServeEngine(c, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                      cache="paged", block_size=BS, n_blocks=n_blocks,
                      decode_window=8)
    budget = MAX_LEN - PROMPT
    reqs = [Request(rid=i, prompt=np.zeros(PROMPT, np.int32),
                    max_new_tokens=budget, arrival_s=0.0)
            for i in range(2)]
    out = eng.serve(reqs, policy="continuous")
    by = out.by_rid()
    # rid 1 really was deferred for rid 0's whole residency
    assert by[1].admitted_s >= by[0].finish_s
    # ...and fused decode windows ran while it waited for blocks
    solo = [r for r in out.steps if r.kind == "decode"
            and set(r.rids) == {0} and r.t1 <= by[1].admitted_s]
    assert solo, "no solo decode windows recorded during the deferral"
    assert max(r.n_steps for r in solo) > 1, \
        "deferred head forced per-token cadence on the solo resident"
    # scheduling change only: outcomes and the pool ledger are untouched
    assert all(r.finish_reason == "length" and len(r.tokens) == budget
               for r in out.results)
    assert eng._paged.free_blocks == n_blocks - 1
    assert eng._slot_cap == {}
