"""Prefill program calls per serve loop iteration that runs any: each
(prompt bucket, prefix depth) group of an iteration is a call of its own,
and the decode step of that iteration waits for all of them (the
engine's ``StepRecord`` counters ``iter`` of its prefill calls)."""
from perfbench import spans


def read(facts, cfg, mix):
    calls = [r for r in spans.engine_calls() if r["kind"] == "prefill"]
    if not calls:
        return None
    return len(calls) / len({r["iter"] for r in calls})
