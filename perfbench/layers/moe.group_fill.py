"""Share of the dropless expert layers' grouped-matmul buffer rows that
hold a routed choice: the real-token choices that landed on the experts
held here (``moe_local`` on the ``serve.emit`` spans) over the buffer
rows the prefill and decode programs held (``moe_rows`` on the
``serve.prefill`` and ``serve.decode`` spans), over the traced run."""
from perfbench import counters


def read(facts, cfg, mix):
    evs = counters.events()
    local = counters.total(evs, ("serve.emit",), "moe_local")
    rows = counters.total(evs, ("serve.prefill", "serve.decode"), "moe_rows")
    if local is None or not rows:
        return None
    return 100.0 * local / rows
