"""The request generator: seeded, the same work for every seed, and the
stated length distributions."""
import json
import pathlib

import numpy as np
import pytest

from perfbench import traffic

MIXES = pathlib.Path(__file__).resolve().parents[1] / "mixes"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def test_same_seed_same_requests():
    a = traffic.generate(mix("chat"), 2**33 + 5, 40, 49152)
    b = traffic.generate(mix("chat"), 2**33 + 5, 40, 49152)
    assert [(r.arrival_s, r.max_new_tokens) for r in a] == \
        [(r.arrival_s, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_gets_the_same_work():
    m = mix("chat")
    a = traffic.generate(m, 1, 40, 49152)
    b = traffic.generate(m, 2, 40, 49152)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    # in the mix's own order, with the seed's token contents
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    gaps = lambda rs: sorted(np.diff([r.arrival_s for r in rs]))  # noqa: E731
    assert len(a) == round(m["arrivals"]["rate_per_s"] * 40)
    assert a[0].arrival_s == 0.0
    assert np.mean(gaps(a)) == pytest.approx(1 / m["arrivals"]["rate_per_s"],
                                             rel=0.15)


def test_length_quantiles():
    q = traffic.lognormal_quantiles(1001, 600, 0.9, 128, 4096)
    assert np.median(q) == 600
    assert q.min() >= 128 and q.max() <= 4096
    r = traffic.lognormal_quantiles(1001, 600, 0.9, 128, 4096, round_up=128)
    assert np.all(r % 128 == 0) and np.median(r) == 640
    # the 84th percentile of a log-normal sits at median * e^sigma
    assert np.percentile(q, 84.13) == pytest.approx(600 * np.e ** 0.9, rel=0.02)


def test_warm_lengths_reach_every_prefill_program():
    m = mix("chat")
    chunk, p = m["engine"]["chunk_tokens"], m["prompt"]
    want = set()
    for p in range(p["min"], p["max"] + 1, p["round_up"]):
        for s in range(0, p, chunk):
            want.add((min(chunk, p - s), s))
    got = set()
    for p, _ in traffic.warm_lengths(m):
        for s in range(0, p, chunk):
            got.add((min(chunk, p - s), s))
    assert got == want
