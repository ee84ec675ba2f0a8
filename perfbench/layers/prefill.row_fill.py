"""Share of the serve prefill calls' padded token grid that holds prompt
tokens: the real prompt tokens of every prefill call over its padded
rows times its prompt bucket, summed over the calls (the engine's
``StepRecord`` counters ``used``, ``rows`` and ``width``). The engine
pads every prefill group to its slot count, and this is what that
padding leaves of the prefill matmuls' work."""
from perfbench import spans


def read(facts, cfg, mix):
    calls = [r for r in spans.engine_calls() if r["kind"] == "prefill"]
    grid = sum(r["rows"] * r["width"] for r in calls)
    if not grid:
        return None
    return 100.0 * sum(r["used"] for r in calls) / grid
