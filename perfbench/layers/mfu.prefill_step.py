"""Useful prompt FLOPs (``counts/lm_flops.prefill_flops`` of every served
prompt: the real tokens only, logits of the last) over the device time of
the prefill programs in the trace times the bf16 peak. The prefill calls
pad every group to the engine's slot count, and that padding shows here."""
from perfbench import readers
from perfbench import trace as tr
from perfbench.counts import lm_flops


def read(facts, cfg, mix):
    t = tr.matching(facts["trace"]["modules"], readers.PREFILL_PROGRAMS)
    done = readers.finished(facts)
    if t <= 0 or not done:
        return None
    f = sum(lm_flops.prefill_flops(cfg, len(r["prompt"])) for r in done)
    return 100.0 * f / (t * readers.peak_flops(facts))
