"""Model FLOP utilization of training: ``(6 N + 12 L H Dh S)`` per token
(``counts/lm_flops.py``; recomputation not counted) times the tokens of
the traced steps, over the traced window times the chips times the bf16
peak."""
from perfbench import readers
from perfbench.counts import lm_flops


def read(facts, cfg, mix):
    w = facts.get("window_s")
    if not w or not facts.get("tokens"):
        return None
    f = lm_flops.train_flops_per_token(cfg, mix["seq_len"]) * facts["tokens"]
    return 100.0 * f / (w * facts["chips"] * readers.peak_flops(facts))
