"""The one request generator every serve mix is read by.

A mix file gives arrival and length distributions as numbers; this turns
them into a request list for one run. The prompt lengths, output lengths
and inter-arrival gaps are stratified quantiles of the stated
distributions, put in an order drawn from the mix's own
``schedule_seed``: every run gets the same schedule (the work is fixed by
the mix and the window), and the run's seed draws the token contents.
A schedule drawn from the run's seed moved `chat`'s TTFT p90 between 2.0
and 4.8 s from seed to seed on a v5e, while two runs of one seed agreed
within 1 %: at ~40 requests a run, which request comes when sets the
tail.

The distributions are those of the program's ``serve/traffic.py`` rules
written out as quantiles: log-normal lengths, clipped and rounded up, and
Poisson (exponential-gap) arrivals.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Spec:
    """One request as the benchmark draws it."""

    rid: int
    arrival_s: float
    prompt: np.ndarray
    max_new_tokens: int


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int, round_up: int = 1) -> np.ndarray:
    """``n`` stratified quantiles of a log-normal (median, sigma), clipped
    to [lo, hi] and rounded up to a multiple of ``round_up``."""
    nd = NormalDist()
    q = np.array([median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
                  for i in range(n)])
    q = np.clip(np.ceil(q), lo, hi)
    q = np.ceil(q / round_up) * round_up
    return np.minimum(q, hi).astype(np.int64)


def exponential_gaps(n: int, rate_per_s: float) -> np.ndarray:
    """``n`` stratified quantiles of the exponential gap of a Poisson
    process at ``rate_per_s``."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate_per_s


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of one run: arrivals over ``seconds`` at the mix's
    rate, first at 0."""
    n = max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))
    p, o = mix["prompt"], mix["output"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                  p["max"], p.get("round_up", 1))
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                  o["max"], o.get("round_up", 1))
    if mix["arrivals"]["kind"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    gaps = exponential_gaps(n, mix["arrivals"]["rate_per_s"])
    order = np.random.default_rng(int(mix["schedule_seed"]))
    prompts, outputs = order.permutation(prompts), order.permutation(outputs)
    gaps = order.permutation(gaps)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[1:])])
    rng = np.random.default_rng(int(seed))
    return [Spec(i, float(arrivals[i]),
                 rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                 int(outputs[i]))
            for i in range(n)]


def warm_lengths(mix: dict) -> list:
    """(prompt, output) pairs that reach every program this mix can reach
    under chunked prefill: every chunk size (a whole chunk, and each
    shorter last chunk the rounding allows) at every prefix depth, and
    the longest context. Served together at time 0."""
    p, o = mix["prompt"], mix["output"]
    chunk = mix["engine"]["chunk_tokens"]
    step = p.get("round_up", 1)
    if chunk % step:
        raise ValueError(f"chunk {chunk} is not a multiple of the prompt "
                         f"rounding {step}")
    lo = -(-p["min"] // step) * step
    # each shorter last chunk r at each depth d: a prompt of d + r
    pairs = [(d + r, o["min"]) for r in range(step, chunk, step)
             for d in range(0, p["max"], chunk) if lo <= d + r <= p["max"]]
    # whole chunks at every depth: the longest prompt of whole chunks
    longest = p["max"] // chunk * chunk
    if longest >= lo:
        pairs.append((longest, o["min"]))
    # a short request that decodes while the longest prefills
    pairs.append((lo, min(o["max"], max(o["min"], longest // chunk + 2))))
    return pairs
