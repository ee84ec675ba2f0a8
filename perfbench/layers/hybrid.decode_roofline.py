"""Roofline share of the hybrid decode program: the least time the chip
could take for the bytes its micro-steps must move
(``counts/hybrid_decode.py``: every weight held, read once; the conv and
SSM state of every batch row, read and written; the live KV blocks that
the ``serve.decode`` spans count as ``used``) at HBM bandwidth, over the
device seconds of the decode program (``jit_step``)."""
from perfbench import readers, spans
from perfbench import trace as tr
from perfbench.counts import hybrid_decode


def read(facts, cfg, mix):
    t = tr.matching(facts["trace"]["modules"], r"^jit_step$")
    calls = [r for r in spans.engine_calls() if r["kind"] == "decode"]
    steps = sum(r["n_steps"] for r in calls)
    if t <= 0 or not steps:
        return None
    nbytes = sum(hybrid_decode.least_bytes(
        cfg, r["rows"], mix["engine"]["block_size"], r["n_steps"], r["used"])
        for r in calls)
    return 100.0 * nbytes / readers.peak_bw(facts) / t
