"""Model FLOP utilization of the hybrid model's prefill programs: the
useful FLOPs of every finished request's prompt (``counts/hybrid_flops.py``:
Mamba projections, conv and SSD math at chunk 256, attention over prefix
and chunk, router and shared expert, the last token's logits) plus the
routed experts' FLOPs of the choices the prefill programs counted
(``moe_local_prefill`` on the ``serve.emit`` spans), over the device time
of the prefill programs times the bf16 peak."""
from perfbench import counters, readers
from perfbench import trace as tr
from perfbench.counts import hybrid_flops


def read(facts, cfg, mix):
    t = tr.matching(facts["trace"]["modules"], readers.PREFILL_PROGRAMS)
    done = readers.finished(facts)
    routed = counters.total(counters.events(), ("serve.emit",),
                            "moe_local_prefill")
    if t <= 0 or not done or routed is None:
        return None
    chunk = mix["engine"]["chunk_tokens"]
    f = sum(hybrid_flops.prompt_flops(cfg, len(r["prompt"]), chunk)
            for r in done) + hybrid_flops.routed_flops(cfg, routed)
    return 100.0 * f / (t * readers.peak_flops(facts))
