"""Arithmetic shared by the per-layer readers in ``layers/``.

``facts`` is what a run hands the readers: the driver's own counts (the
finished requests and engine steps of a serve run, the steps and tokens
of a training window), ``trace`` (the reduction of ``trace.py``),
``window_s`` (the traced window's length on the host clock), ``chips``
and ``device``.
"""
from __future__ import annotations

from perfbench import peaks as pk
from perfbench import trace as tr
from perfbench.counts import paged_prefill

#: the trace's names (``trace.short``) of the paged prefill kernel's
#: calls and of the serve prefill programs
PREFILL_KERNEL = r"^jit_prefill_hit:custom-call$"
PREFILL_PROGRAMS = r"^jit_(serve_prefill|prefill_hit)$"


def peak_flops(facts: dict) -> float:
    return pk.peaks(facts["device"]["kind"])["bf16_flops"]


def peak_bw(facts: dict) -> float:
    return pk.peaks(facts["device"]["kind"])["hbm_bytes_per_s"]


def idle_pct(facts: dict):
    w = facts.get("window_s")
    if not w:
        return None
    return 100.0 * (1.0 - facts["trace"]["busy_s"] / w)


def finished(facts: dict) -> list:
    return [r for r in facts["results"] if r["reason"] == "length"
            and len(r["tokens"]) == r["want"]]


def prefill_chunks(mix: dict, prompt_len: int) -> list:
    """(prefix, chunk) of each chunk after the first: the chunks that
    attend a cached prefix through the paged prefill kernel."""
    c = mix["engine"]["chunk_tokens"]
    return [(s, min(c, prompt_len - s)) for s in range(c, prompt_len, c)]


def roofline_pct(facts: dict, flops: float, nbytes: float, kernel: str):
    t = tr.matching(facts["trace"]["ops"], kernel)
    if t <= 0 or flops <= 0:
        return None
    least = max(flops / peak_flops(facts), nbytes / peak_bw(facts))
    return 100.0 * least / t


def prefill_kernel_work(facts: dict, cfg: dict, mix: dict):
    f = b = 0.0
    for r in finished(facts):
        for p, c in prefill_chunks(mix, len(r["prompt"])):
            f += paged_prefill.flops(cfg, p, c)
            b += paged_prefill.bytes_moved(cfg, p, c)
    n = cfg["num_hidden_layers"]
    return n * f, n * b
