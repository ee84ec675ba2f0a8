"""Device time of the paged decode program per decode micro-step: the
seconds of the ``jit_step`` program in the trace over the micro-steps
that the engine's decode calls ran (``n_steps`` of its ``serve.decode``
spans), in milliseconds. Everything the decode program does on the
device counts, attention and matmuls as well as whatever it does to the
KV pool around them."""
from perfbench import spans
from perfbench import trace as tr


def read(facts, cfg, mix):
    t = tr.matching(facts["trace"]["modules"], r"^jit_step$")
    steps = sum(r["n_steps"] for r in spans.engine_calls()
                if r["kind"] == "decode")
    if t <= 0 or not steps:
        return None
    return 1000.0 * t / steps
