"""Median wait of a request between its due time and its admission to a
slot (``admitted_s - arrival_s`` of the engine's results)."""
from perfbench import readers, stats


def read(facts, cfg, mix):
    w = [(r["admitted"] - r["arrival"]) * 1e3 for r in readers.finished(facts)]
    return stats.percentile(w, 50) if w else None
