"""The serve engine's step counters and spans.

Every prefill and decode program call leaves a ``StepRecord`` that counts
its work (``iter``, ``rows``, ``width``, ``used``) and says whether its
window ends in a fetch; the first call of each program key runs inside
``serve.first_call``. The counts here
are worked out by hand from the requests. A fake clock makes the late
arrival land on a fixed loop iteration: the engine sleeps (in 0.05 s
steps) only once every earlier request has finished. Recording spans
under the profiler must leave greedy streams bit-identical.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import ServeEngine
from repro.serve.requests import Request

_CONFIG = get_config("llama3.2-3b").reduced(dtype="float32",
                                            param_dtype="float32")
#: (prompt tokens, new tokens, arrival s): three at once, one late
SHAPES = [(20, 6, 0.0), (40, 4, 0.0), (64, 3, 0.0), (24, 2, 1.0)]


@pytest.fixture(scope="module")
def setup():
    return _CONFIG, lm.init(jax.random.key(0), _CONFIG)


def _requests(c, shapes=SHAPES):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(0, c.vocab, p, np.int32),
                    max_new_tokens=n, arrival_s=a)
            for i, (p, n, a) in enumerate(shapes)]


def _engine(setup, sched):
    c, params = setup
    t = [0.0]
    return ServeEngine(c, params, cache="paged", n_slots=3, max_len=96,
                       block_size=16, chunk_tokens=32, decode_window=8,
                       sched=sched, clock=lambda: t[0],
                       sleep_fn=lambda s: t.__setitem__(0, t[0] + s))


def _counts(out):
    return [(s.kind, s.iter, s.rids, s.rows, s.width, s.used, s.n_steps)
            for s in out.steps]


def test_chunked_counters_match_hand_counts(setup):
    eng = _engine(setup, "chunked")
    out = eng.serve(_requests(setup[0]))
    assert _counts(out) == [
        # iter 0: the first 32-token chunk of each prompt, each in a
        # call of its own at (32, 0): 20, 32 and 32 real tokens in 1 x
        # 32; rid 0 is prefilled
        ("prefill", 0, (0,), 1, 32, 20, 1),
        ("prefill", 0, (1,), 1, 32, 32, 1),
        ("prefill", 0, (2,), 1, 32, 32, 1),
        # rid 0 decodes alone (a chunk is pending: k = 1) at pos 20:
        # 2 live blocks, every slot owns 2, nb = 2
        ("decode", 0, (0,), 3, 2, 2, 1),
        # iter 1: rid 1's last 8 tokens and rid 2's last 32 after the
        # 2 cached blocks, at (16, 2) and (32, 2)
        ("prefill", 1, (1,), 1, 16, 8, 1),
        ("prefill", 1, (2,), 1, 32, 32, 1),
        # all three decode, k = 2 (rid 2's budget); rid 2 grows to 5
        # blocks, nb = 6 (capped at max_len / 16); live blocks per
        # step: rid 0 at pos 21, 22: 2 + 2; rid 1 at 40, 41: 3 + 3;
        # rid 2 at 64, 65: 5 + 5
        ("decode", 1, (0, 1, 2) * 2, 3, 6, 20, 2),
        # a pending arrival keeps k = 1; rid 0 at 23 (2), rid 1 at 42 (3)
        ("decode", 2, (0, 1), 3, 4, 5, 1),
        ("decode", 3, (0,), 3, 2, 2, 1),
        # iters 4-23 wait out the arrival at 1.0 s; rid 3 at iter 24
        ("prefill", 24, (3,), 1, 32, 24, 1),
        ("decode", 24, (3,), 3, 2, 2, 1),
    ]
    # rid 1's and rid 2's first chunks emit nothing, so their windows
    # end without a fetch; every other window fetches
    assert [s.fetched for s in out.steps] == [
        True, False, False, True, True, True, True, True, True, True, True]


def test_phased_counters_match_hand_counts(setup):
    eng = _engine(setup, "phased")
    out = eng.serve(_requests(setup[0]))
    assert _counts(out) == [
        # every whole prompt in its own bucket: three calls in iter 0
        ("prefill", 0, (0,), 3, 32, 20, 1),
        ("prefill", 0, (1,), 3, 48, 40, 1),
        ("prefill", 0, (2,), 3, 64, 64, 1),
        ("decode", 0, (0, 1, 2) * 2, 3, 6, 20, 2),
        ("decode", 1, (0, 1), 3, 4, 5, 1),
        ("decode", 2, (0,), 3, 2, 2, 1),
        ("decode", 3, (0,), 3, 2, 2, 1),
        ("prefill", 24, (3,), 3, 32, 24, 1),
        ("decode", 24, (3,), 3, 2, 2, 1),
    ]


def test_non_final_chunk_window_ends_without_a_fetch(setup):
    eng = _engine(setup, "chunked")
    out = eng.serve(_requests(setup[0], [(64, 2, 0.0)]))
    pre = [s for s in out.steps if s.kind == "prefill"]
    # chunk 1 of 2 emits nothing and fetches nothing; chunk 2 fetches
    # the first token
    assert [(s.n_tokens, s.fetched) for s in pre] == [(0, False), (1, True)]


def _traced_span_names(fn, d):
    jax.profiler.start_trace(str(d))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    prof = jax.profiler.ProfileData.from_file(
        str(next(d.glob("**/*.xplane.pb"))))
    return [e.name for p in prof.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]


def test_first_call_span_marks_only_a_program_s_first_call(setup, tmp_path):
    eng = _engine(setup, "chunked")
    first = _traced_span_names(lambda: eng.serve(_requests(setup[0])),
                               tmp_path / "first")
    again = _traced_span_names(lambda: eng.serve(_requests(setup[0])),
                               tmp_path / "again")
    # prefill (32, 0), (16, 2), (32, 2); decode nb 2, 6, 4; the same
    # requests again call only programs already built
    assert first.count("serve.first_call") == 6
    assert "serve.iter" in again and "serve.first_call" not in again


@pytest.mark.parametrize("sched", ["chunked", "phased"])
def test_greedy_streams_unchanged_with_the_profiler_on(setup, sched,
                                                       tmp_path):
    off = _engine(setup, sched).serve(_requests(setup[0]))
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = _engine(setup, sched).serve(_requests(setup[0]))
    finally:
        jax.profiler.stop_trace()
    assert {r.rid: r.tokens for r in on.results} == \
        {r.rid: r.tokens for r in off.results}
    assert _counts(on) == _counts(off)
