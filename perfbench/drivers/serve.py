"""Serve cells: the program's ``ServeEngine`` (paged KV pool, chunked
prefill) serving an open-loop request stream in real time.

Set-up makes the weights from the seed on the device, builds the engine
with the mix's settings, and warms every program the mix can reach: one
``serve`` call over requests whose lengths cross every (chunk size,
prefix depth) prefill program and the decode gather widths it passes,
then any decode width the mix reaches that the call did not. The window
is one ``serve`` call over the run's requests (``traffic.py``), arriving
at the mix's fixed rate; it ends when the last request has finished.

Each request is timed from its due time: time to first token, the mean
gap between its output tokens, and output tokens over the run. ``correct``
compares, for a seeded sample of the finished requests (the longest among
them), every served token against the configuration's plain float32
reference (``model.reference_of``) run over
the prompt and the served tokens: the widest gap by which a served
token's reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import gc
import time

import jax.numpy as jnp
import numpy as np

from perfbench import stats, traffic
from perfbench.model import model_config, reference_of

#: what this driver calls of the configuration's plain reference
REFERENCE_CALLS = ("make_weights", "sequence_logits")


def make_engine(ctx, c, params):
    from repro.serve.engine import ServeEngine
    eng = ctx.mix["engine"]

    def sleep(s):
        with ctx.span("bench.engine_sleep"):
            time.sleep(s)

    return ServeEngine(c, params, n_slots=eng["n_slots"],
                       max_len=eng["max_len"], cache="paged",
                       sched="chunked", block_size=eng["block_size"],
                       chunk_tokens=eng["chunk_tokens"],
                       clock=time.perf_counter, sleep_fn=sleep)


def requests_of(specs):
    from repro.serve.requests import Request
    return [Request(rid=s.rid, prompt=s.prompt,
                    max_new_tokens=s.max_new_tokens, arrival_s=s.arrival_s)
            for s in specs]


def decode_widths(mix: dict) -> list:
    """Every decode gather width a request of this mix can need: the
    engine gathers the next power of two of blocks above the longest live
    slot's, capped at a slot's blocks."""
    eng, p, o = mix["engine"], mix["prompt"], mix["output"]
    bs = eng["block_size"]
    cap = eng["max_len"] // bs
    lo = -(-(p["min"] + 1) // bs)
    hi = min(-(-(p["max"] + o["max"]) // bs), cap)
    return sorted({min(1 << (n - 1).bit_length(), cap)
                   for n in range(lo, hi + 1)})


def warm(ctx, engine) -> None:
    specs = [traffic.Spec(-(i + 1), 0.0, np.zeros(plen, np.int32), n)
             for i, (plen, n) in enumerate(traffic.warm_lengths(ctx.mix))]
    with ctx.span("bench.warm"):
        engine.serve(requests_of(specs))
        # the warm requests cross only some decode widths: drive the
        # engine's own program once for each the mix can reach, on the
        # trash block
        n = engine.n_slots
        for nb in decode_widths(ctx.mix):
            fn = engine._paged_step_fn(nb)
            tables = jnp.zeros((n, engine._paged.max_blocks), jnp.int32)
            tok, engine.caches = fn(engine.params, jnp.zeros((n,), jnp.int32),
                                    engine.caches, jnp.zeros((n,), jnp.int32),
                                    tables)
            tok.block_until_ready()


def served_gaps(cfg, params, results, max_len: int, quant=None) -> list:
    """Per request, the gap of each served token under the configuration's
    float32 reference: reference best logit minus the served token's. With
    ``quant`` the served tokens are replaced by the argmax of the
    reference computed at that precision (the control)."""
    ref = reference_of(cfg)
    out = []
    for r in results:
        seq = np.concatenate([r["prompt"], np.asarray(r["tokens"], np.int32)])
        pad = np.zeros((1, max_len), np.int32)
        pad[0, :len(seq)] = seq
        lo, n = len(r["prompt"]) - 1, len(r["tokens"])
        lg = ref.sequence_logits(cfg, params, jnp.asarray(pad))[0]
        lg = np.asarray(lg[lo:lo + n])
        if quant is None:
            tok = np.asarray(r["tokens"])
        else:
            lq = ref.sequence_logits(cfg, params, jnp.asarray(pad), quant)[0]
            tok = np.asarray(jnp.argmax(lq[lo:lo + n], -1))
        out.append(lg.max(-1) - lg[np.arange(n), tok])
    return out


def sample_finished(done: list, k: int, seed: int) -> list:
    """A seeded sample of ``k`` finished requests, the longest among them."""
    longest = max(done, key=lambda r: len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def serve_window(ctx, engine, reqs):
    ctx.open_window()
    if ctx.trace:
        ctx.start_trace()
    with ctx.span("bench.window"):
        out = engine.serve(reqs)
    if ctx.trace:
        ctx.stop_trace()
    ctx.close_window()
    return out


def run(ctx) -> dict:
    cfg, mix = ctx.cfg, ctx.mix
    ref = reference_of(cfg)
    c = model_config(cfg)
    from repro.models import lm
    with ctx.span("bench.build"):
        params = ref.make_weights(lm.init_abstract(c), ctx.seed)
        engine = make_engine(ctx, c, params)
        warm(ctx, engine)
    specs = traffic.generate(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    prompts = {s.rid: s.prompt for s in specs}
    out = serve_window(ctx, engine, requests_of(specs))
    ctx.read_memory()
    res = [{"rid": r.rid, "prompt": prompts[r.rid], "tokens": list(r.tokens),
            "want": next(s.max_new_tokens for s in specs if s.rid == r.rid),
            "arrival": r.arrival_s, "admitted": r.admitted_s,
            "first": r.first_token_s, "finish": r.finish_s,
            "reason": r.finish_reason}
           for r in out.results]
    steps = [(s.kind, s.t0, s.t1, len(s.rids), s.n_tokens,
              getattr(s, "n_steps", 1)) for s in out.steps]
    preempt = engine.preemptions
    del engine, out
    gc.collect()   # the engine's programs close over it: free its pool now
    ok = [r for r in res if r["reason"] == "length"
          and len(r["tokens"]) == r["want"]]
    failed = len(specs) - len(ok)
    inf = float("inf")
    ttft = [(r["first"] - r["arrival"]) * 1e3 if r in ok else inf for r in res]
    tpot = [(r["finish"] - r["first"]) / (len(r["tokens"]) - 1) * 1e3
            for r in ok if len(r["tokens"]) > 1]
    t0 = min(r["arrival"] for r in res)
    t1 = max(r["finish"] for r in res)
    n_out = sum(len(r["tokens"]) for r in ok)
    setup_s = ctx.window_start - ctx.t_start
    waits = sorted((r["admitted"] - r["arrival"]) * 1e3 for r in ok)
    ctx.notes.update(requests=len(specs), failed=failed, out_tokens=n_out,
                     run_s=t1 - t0, preemptions=preempt,
                     queue_wait_ms_p50=stats.percentile(waits, 50) if waits
                     else None,
                     queue_wait_ms_max=waits[-1] if waits else None)

    t_ref = time.perf_counter()
    chk = mix["check"]
    sample = sample_finished(ok, chk["sample"], ctx.seed) if ok else []
    gaps = served_gaps(cfg, params, sample, mix["engine"]["max_len"])
    widest = max((float(g.max()) for g in gaps), default=inf)
    ctx.notes.update(reference_s=time.perf_counter() - t_ref,
                     checked_tokens=int(sum(len(g) for g in gaps)))
    return {
        "attempted": len(specs), "failed": failed,
        "e2e": {"ttft_p90_ms": stats.percentile(ttft, 90),
                "tpot_p90_ms": stats.percentile(tpot, 90) if tpot else inf,
                "serve_out_tokens_per_s": stats.rate(n_out, t0, t1),
                "setup_s": setup_s},
        "checks": [("served_logit_gap", widest, ctx.limits["served_logit_gap"])],
        "facts": {"results": res, "steps": steps, "t0": t0, "t1": t1,
                  "n_slots": mix["engine"]["n_slots"]},
        "control_inputs": (params, sample),
    }


def control(ctx, out) -> dict:
    """The control's number against the cell's limit: at each served
    position of the same sample, the gap of the token that the reference
    computed from fp8 operands puts first."""
    params, sample = out["control_inputs"]
    gaps = served_gaps(ctx.cfg, params, sample, ctx.mix["engine"]["max_len"],
                       quant="fp8")
    return {"fp8": [("served_logit_gap", max(float(g.max()) for g in gaps),
                     ctx.limits["served_logit_gap"])]}
