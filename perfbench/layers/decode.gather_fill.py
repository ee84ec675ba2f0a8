"""Share of the paged decode gathers that holds live KV: the blocks each
live slot's attention needs at each fused micro-step (capped at the
gather width), over the blocks gathered, which are the gather width for
every row of the batch at every micro-step (the engine's ``StepRecord``
counters ``used``, ``rows``, ``width`` and ``n_steps``)."""
from perfbench import spans


def read(facts, cfg, mix):
    calls = [r for r in spans.engine_calls()
             if r["kind"] == "decode" and r["width"]]
    gathered = sum(r["rows"] * r["width"] * r["n_steps"] for r in calls)
    if not gathered:
        return None
    return 100.0 * sum(r["used"] for r in calls) / gathered
