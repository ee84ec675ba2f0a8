"""Chunked prefill + block-granular preemption (the iteration-level
scheduler).

The contract mirrors test_paged_serve.py's: chunked scheduling is a
*performance* feature — token streams must be bit-identical to the
phased path, including across preempt/resume cycles (the resume replays
its emitted tail through the decode program precisely so that every KV
row is rebuilt by the program that built it the first time). Everything
runs the small float32 model so greedy argmax never flakes on bf16 ties.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import ServeEngine
from repro.serve.requests import Request, RequestResult
from repro.serve.scheduler import Scheduler

N_SLOTS, MAX_LEN, BS = 3, 96, 16

_CONFIG = get_config("llama3.2-3b").reduced(dtype="float32",
                                            param_dtype="float32")


@pytest.fixture(scope="module")
def setup():
    return _CONFIG, lm.init(jax.random.key(0), _CONFIG)


def _engine(setup, **kw):
    c, params = setup
    kw.setdefault("n_slots", N_SLOTS)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("block_size", BS)
    kw.setdefault("decode_window", 8)
    return ServeEngine(c, params, cache="paged", **kw)


def _streams(out):
    return {r.rid: (r.tokens, r.finish_reason) for r in out.results}


# ---------------------------------------------------------------------------
# Stream bit-identity: chunked == phased
# ---------------------------------------------------------------------------


def test_chunked_streams_match_phased_mixed_prompts(setup):
    """Mixed prompt lengths — shorter than, equal to, and spanning
    several chunk_tokens slices — generate identical streams under both
    schedulers (ample pool: no preemption in play yet)."""
    c, _ = setup
    rng = np.random.default_rng(11)
    shapes = [(5, 40), (20, 30), (40, 20), (64, 10)]
    reqs = [Request(rid=i, prompt=rng.integers(0, c.vocab, p, np.int32),
                    max_new_tokens=b, arrival_s=0.0)
            for i, (p, b) in enumerate(shapes)]
    eng = _engine(setup)
    phased = eng.serve(list(reqs), policy="continuous", sched="phased")
    chunked = eng.serve(list(reqs), policy="continuous", sched="chunked")
    assert _streams(chunked) == _streams(phased)
    assert eng.preemptions == 0
    assert eng._paged.free_blocks == eng._paged.n_blocks - 1


def test_chunked_streams_match_phased_with_prefix_cache(setup):
    """Chunked prefill reuses the suffix-prefill program for its
    non-first chunks AND for prefix-index hits — the combination must
    still be invisible in the streams, and a late arrival sharing a
    full-block prefix must actually hit the index under chunked."""
    c, _ = setup
    rng = np.random.default_rng(13)
    shared = rng.integers(0, c.vocab, 48, np.int32)
    tails = rng.integers(0, c.vocab, (2, 8), np.int32)
    reqs = [Request(rid=i, prompt=np.concatenate([shared, tails[i]]),
                    max_new_tokens=20) for i in range(2)]

    plain = _engine(setup)
    want = _streams(plain.serve(list(reqs), policy="continuous",
                                sched="phased"))
    pref = _engine(setup, prefix_cache=True)
    for mode in ("phased", "chunked"):
        pref.reset_prefix_cache()
        # two serve() calls against the persistent index: the second
        # request deterministically finds the first one's registered
        # prefix (a same-wave admission could race the registration)
        out0 = pref.serve([reqs[0]], policy="continuous", sched=mode)
        out1 = pref.serve([reqs[1]], policy="continuous", sched=mode)
        assert {**_streams(out0), **_streams(out1)} == want, mode
        assert pref.prefix_stats["hit_requests"] == 1, mode


class _Spans:
    """Stands in for ``TraceAnnotation``: records each span's kwargs."""

    seen: list = []

    def __init__(self, name, **kw):
        _Spans.seen.append((name, kw))

    def set_metadata(self, **kw):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def test_slots_at_one_bucket_and_depth_prefill_in_calls_of_their_own(
        setup, monkeypatch):
    """Four prompts of 66-80 tokens chunk in step: every iteration finds
    all four at one (bucket, depth), and each slot's slice runs in a
    batch-1 call of its own — the programs still keyed by (bucket,
    depth) alone — with streams bit-identical to phased."""
    import repro.serve.engine as E
    c, _ = setup
    rng = np.random.default_rng(29)
    reqs = [Request(rid=i, prompt=rng.integers(0, c.vocab, p, np.int32),
                    max_new_tokens=8, arrival_s=0.0)
            for i, p in enumerate([66, 70, 75, 80])]
    want = _streams(_engine(setup, n_slots=4).serve(
        list(reqs), policy="continuous", sched="phased"))
    _Spans.seen = []
    monkeypatch.setattr(E, "TraceAnnotation", _Spans)
    out = _engine(setup, n_slots=4).serve(list(reqs), policy="continuous",
                                          sched="chunked")
    assert _streams(out) == want
    pre = [s for s in out.steps if s.kind == "prefill"]
    # three 32-token-bucketed slices each: (32, 0), (32, 2), (16, 4)
    assert [(s.iter, s.rids) for s in pre] == [
        (it, (rid,)) for it in range(3) for rid in range(4)]
    assert all(s.rows == 1 and s.used <= s.width for s in pre)
    assert [s.used for s in pre] == [32] * 8 + [2, 6, 11, 16]
    spans = [kw for n, kw in _Spans.seen if n == "serve.prefill"]
    reached = {(kw["bucket"], kw["npre"]) for kw in spans}
    assert reached == {(32, 0), (32, 2), (16, 4)}
    first = {(kw["bucket"], kw["npre"]) for n, kw in _Spans.seen
             if n == "serve.first_call" and "bucket" in kw}
    assert first == reached


# ---------------------------------------------------------------------------
# Preemption: oversubscribed pool completes, streams stay bit-identical
# ---------------------------------------------------------------------------


def _oversubscribed(setup):
    """Two near-max requests against a 5-usable-block pool: worst-case
    demand is 3 + 3 blocks, so phased can only serve them serially while
    chunked admits both optimistically and preempts the younger when its
    decode growth overruns the pool."""
    c, _ = setup
    rng = np.random.default_rng(17)
    prompts = rng.integers(0, c.vocab, (2, 5), np.int32)
    reqs = [Request(rid=0, prompt=prompts[0], max_new_tokens=43),
            Request(rid=1, prompt=prompts[1], max_new_tokens=40)]
    eng = _engine(setup, n_slots=2, max_len=64, n_blocks=6)
    return eng, reqs


def test_preemption_forcing_pool_completes_where_phased_defers(setup):
    eng, reqs = _oversubscribed(setup)
    phased = eng.serve(list(reqs), policy="continuous", sched="phased")
    by_p = phased.by_rid()
    # phased has no move but deferral: rid 1 waits out rid 0's lifetime
    assert eng.preemptions == 0
    assert by_p[1].admitted_s >= by_p[0].finish_s

    chunked = eng.serve(list(reqs), policy="continuous", sched="chunked")
    by_c = chunked.by_rid()
    # chunked admits rid 1 immediately and evicts it when the pool runs
    # dry — it resumes and still completes its full budget
    assert eng.preemptions >= 1
    assert by_c[1].first_token_s < by_c[0].finish_s
    assert all(r.finish_reason == "length" for r in chunked.results)
    assert len(by_c[0].tokens) == 43 and len(by_c[1].tokens) == 40
    # the preempted-then-resumed stream is bit-identical to the
    # never-preempted (phased) one — the decode-replay guarantee
    assert _streams(chunked) == _streams(phased)
    # FIFO survives eviction: the older request finishes first
    assert by_c[0].finish_s <= by_c[1].finish_s
    # pool fully drained, reservation ledger empty
    assert eng._paged.free_blocks == eng._paged.n_blocks - 1
    assert eng._slot_cap == {}


def test_replay_windows_keep_token_accounting_exact(setup):
    """Replay steps burn compute (rids credited) but emit nothing
    (n_tokens counts only appended tokens): totals must balance and
    replay must force per-token windows (forced host-side inputs can't
    ride a fused on-device argmax chain)."""
    eng, reqs = _oversubscribed(setup)
    out = eng.serve(list(reqs), policy="continuous", sched="chunked")
    assert eng.preemptions >= 1
    total_gen = sum(r.n_tokens for r in out.results)
    credited = sum(s.n_tokens for s in out.steps)
    assert credited == total_gen
    for rec in out.steps:
        if rec.kind == "decode":
            assert rec.n_tokens <= len(rec.rids)
            assert len(rec.rids) % rec.n_steps == 0


def test_preemption_with_pinned_prefix_index_completes(setup):
    """Eviction composes with prefix-index refcounts: a registered
    block stays pinned across its owner finishing, and preemption's
    reclaim must still free enough to complete every request — with
    streams equal to a roomy phased run."""
    c, _ = setup
    rng = np.random.default_rng(19)
    prompts = rng.integers(0, c.vocab, (2, 16), np.int32)
    reqs = [Request(rid=0, prompt=prompts[0], max_new_tokens=32),
            Request(rid=1, prompt=prompts[1], max_new_tokens=32)]

    roomy = _engine(setup, n_slots=2, max_len=64, prefix_cache=True)
    want = _streams(roomy.serve(list(reqs), policy="continuous",
                                sched="phased"))
    tight = _engine(setup, n_slots=2, max_len=64, n_blocks=6,
                    prefix_cache=True)
    out = tight.serve(list(reqs), policy="continuous", sched="chunked")
    assert tight.preemptions >= 1
    assert tight.prefix_stats["registered_blocks"] >= 1
    assert all(r.finish_reason == "length" for r in out.results)
    assert _streams(out) == want
    # index pins survive the run but count as reclaimable headroom
    assert tight._paged.available_blocks == tight._paged.n_blocks - 1


# ---------------------------------------------------------------------------
# Admission-side preemption + FIFO (scheduler/engine unit level)
# ---------------------------------------------------------------------------


def test_admission_preempts_younger_running_slot(setup):
    """_admit_paged under chunked: a queue head older than a running
    slot reclaims that slot's blocks instead of deferring behind it.
    The victim re-queues at the FRONT carrying its emitted history as a
    replay tail."""
    eng = _engine(setup, n_slots=2, max_len=64, n_blocks=5,
                  decode_window=1)
    eng._ensure_cache()
    sched = Scheduler(2, 64)
    young = Request(rid=1, prompt=np.zeros(5, np.int32),
                    max_new_tokens=40, arrival_s=1.0)
    sched.submit(young)
    (yslot,) = sched.refill(2.0)
    eng._slot_cap[yslot.index] = 1
    eng._paged.ensure(yslot.index, 33)          # grown to 3 of 4 blocks
    yslot.prefill_pos = 5
    yslot.generated, yslot.pos, yslot.last_token = 7, 11, 6
    results = {0: RequestResult(rid=0, prompt_len=20),
               1: RequestResult(rid=1, prompt_len=5)}
    results[1].tokens = list(range(7))

    old = Request(rid=0, prompt=np.zeros(20, np.int32),
                  max_new_tokens=30, arrival_s=0.0)
    sched.submit(old)
    (oslot,) = sched.refill(2.0)
    ok = eng._admit_paged(sched, [oslot], results, chunked=True)

    assert ok == [oslot] and oslot.request is old
    assert eng.preemptions == 1
    assert eng._paged.owned(yslot.index) == 0
    assert eng._slot_cap == {oslot.index: 2}    # ceil(21 / 16)
    resume = sched.queue[0]
    assert resume.rid == 1 and resume.resumed
    assert resume.n_replay == 7 and resume.prompt_len == 5 + 7
    assert resume.max_new_tokens == 40 - 7
    assert [int(t) for t in resume.prompt[5:]] == list(range(7))


def test_unadmit_mid_chunked_prefill_preserves_fifo(setup):
    """A long prompt chunk-prefills while the pool is too tight for the
    whole wave: the tail unadmits back to the queue front and service
    order (admitted_s) still follows arrival order, with streams equal
    to phased."""
    c, _ = setup
    rng = np.random.default_rng(23)
    reqs = [Request(rid=0, prompt=rng.integers(0, c.vocab, 64, np.int32),
                    max_new_tokens=15, arrival_s=0.0),
            Request(rid=1, prompt=rng.integers(0, c.vocab, 5, np.int32),
                    max_new_tokens=10, arrival_s=0.0),
            Request(rid=2, prompt=rng.integers(0, c.vocab, 5, np.int32),
                    max_new_tokens=10, arrival_s=0.0)]
    eng = _engine(setup, n_slots=3, max_len=80, n_blocks=7)
    phased = eng.serve(list(reqs), policy="continuous", sched="phased")
    chunked = eng.serve(list(reqs), policy="continuous", sched="chunked")
    assert _streams(chunked) == _streams(phased)
    by = chunked.by_rid()
    assert by[2].queue_s > 0                    # rid 2 really was deferred
    assert by[0].admitted_s <= by[1].admitted_s <= by[2].admitted_s
    assert all(r.finish_reason == "length" for r in chunked.results)
    assert eng._paged.free_blocks == eng._paged.n_blocks - 1
