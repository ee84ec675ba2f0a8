"""Multi-tenant SLO serving workload: goodput + Wh-per-SLO-met-request.

The MLPerf-Power framing on top of the continuous-batching engine: drive
the ServeEngine with seeded multi-tenant traces (``serve.traffic``
presets — Poisson mixes, MMPP bursts, shared system-prompt populations)
and score every request against its tenant's TTFT/TPOT SLO
(``serve.slo``), per (trace x cache) cell:

  goodput             fraction of requests meeting BOTH targets
  ttft_p99 / tpot_p99 tail latency (nearest-rank, includes queueing)
  wh_per_slo_request  attributed energy / SLO-met requests — energy
                      per *useful* inference, the figure the paper's
                      energy-efficiency story reduces to under SLOs

The ``cache`` axis isolates prefix caching: ``paged`` is the plain
block-table pool, ``paged+prefix`` adds the block-granular shared-prefix
index (``PagedKVCache.enable_prefix_cache``) — prompts whose leading
blocks hit the index adopt the shared KV and prefill only their suffix.
On the ``shared_prefix`` trace (two assistant tenants sharing a 48-token
system prompt) that cuts the prefill bucket from 64 to 16 tokens for
every hit, which shows up directly in ``ttft_p99`` and
``wh_per_slo_request``; the ``*_vs_paged`` ratios make the win a gated
record column. Token streams are bit-identical to the non-cached path
(asserted in tests/test_prefix_cache.py), so the comparison is pure
performance, never quality.

The ``sched`` axis isolates iteration-level scheduling on the same
engine: ``phased`` prefills an admitted prompt whole and reserves every
request's worst-case block footprint at admission; ``chunked``
interleaves block-aligned ``chunk_tokens`` slices with decode steps,
admits optimistically, and backs both admission and decode growth with
block-granular preemption (``ServeEngine`` module docstring). The
``long_prefill`` trace runs against a deliberately TIGHT pool
(``POOL_BY_TRACE``): long-generation requests make phased hold
6-block reservations for whole request lifetimes, so documents (and
everything FIFO-queued behind them) defer for tens of milliseconds,
while chunked evicts the youngest generation and admits immediately —
the ttft_p99 collapse the ``*_vs_phased`` ratios gate per
(trace x cache) cell. ``stream_hash`` (order-independent digest of
every per-request token stream) rides along so any sched- or
cache-induced token divergence is visible in the row — preemption
included: a resumed request replays its emitted tail through the
decode program, keeping streams bit-identical (the property
tests/test_chunked_serve.py pins). ``preemptions`` counts chunked
eviction events (nonzero only on the oversubscribed long_prefill
cells).

The ``kv_dtype`` axis rides the plain-paged phased cells only (the
serve workload owns the full int8 cross): same traces and SLOs on an
int8-quantized pool, with ``pool_bytes``/``max_concurrency`` carrying
the capacity story and ``kv_stream_prefix_agreement`` the stream
quality vs the fp32 twin.

SLO targets are deliberately generous for the reduced-config CPU cell
(~10x steady-state latency): goodput sits at 1.0 and acts as a canary —
only a scheduler stall or admission bug pushes it down — while the
discriminating signal lives in the tail-latency and energy columns.
"""
from __future__ import annotations

import hashlib

import jax

from repro.bench.context import Measurement
from repro.bench.spec import workload
from repro.configs import get_config
from repro.core.params import Space
from repro.models import lm
from repro.serve.engine import ServeEngine
from repro.serve.slo import SLO, evaluate_slo
from repro.serve.traffic import TRACE_NAMES, generate_trace, preset_trace

from repro.bench.workloads.serve import stream_agreement

MAX_LEN = 96            # slot capacity (prompt + budget; see traffic presets)
BLOCK_SIZE = 16         # paged KV block; shared_prefix pins 3 full blocks
N_SLOTS = 4
N_REQUESTS = 96
N_REQUESTS_SMOKE = 48
SEED = 0

#: generous CPU-cell targets (~30x the reduced-config steady-state tail:
#: measured ttft_p99 ~0.06 s, tpot_p99 ~0.003 s). Interactive tenants
#: get the tight budget; batch-flavored tenants (bursty "batch",
#: shared_prefix "misc") tolerate double.
SLO_TIGHT = SLO(ttft_s=2.0, tpot_s=0.2)
SLO_RELAXED = SLO(ttft_s=4.0, tpot_s=0.4)
#: batch-flavored tenants tolerate double; long_prefill's "doc" tenant
#: is offline-flavored AND pays an unavoidable 5-chunk prefill
SLO_BY_TENANT = {"batch": SLO_RELAXED, "misc": SLO_RELAXED,
                 "doc": SLO_RELAXED}

#: per-trace paged-pool override (blocks). long_prefill runs against a
#: TIGHT pool: 17 blocks = trash + 16 usable, so two live worst-case
#: generations (6 blocks each) plus a document prompt (6) oversubscribe
#: it — the regime where phased defers admissions behind gen lifetimes
#: and chunked preempts its way through (see the traffic preset
#: comment). Other traces keep the engine's ample default pool.
POOL_BY_TRACE = {"long_prefill": 17}


def _stream_hash(results) -> str:
    """Order-independent sha1 over {rid: tokens}: completion order (and
    therefore results-list order) differs across scheduler modes, so the
    digest sorts by rid before hashing."""
    blob = repr(sorted((r.rid, tuple(r.tokens)) for r in results))
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def _engine(ctx, arch: str, cache: str,
            n_blocks=None, kv_dtype: str = "fp32") -> ServeEngine:
    def make():
        c = get_config(arch).reduced()
        params = lm.init(jax.random.key(SEED), c)
        engine = ServeEngine(c, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                             cache="paged", block_size=BLOCK_SIZE,
                             n_blocks=n_blocks,
                             prefix_cache=cache == "paged+prefix",
                             kv_dtype=kv_dtype,
                             power_methods=ctx.power_methods)
        return c, engine

    return ctx.memo(("serve_slo", arch, cache, n_blocks, kv_dtype), make)


@workload(
    "serve_slo",
    analog="multi-tenant SLO serving: goodput + Wh/SLO-met-request "
           "(MLPerf-Power style), prefix-cached prefill",
    space=Space({"arch": ["llama3.2-3b"], "trace": list(TRACE_NAMES),
                 "cache": ["paged", "paged+prefix"],
                 # int8 pools ride only the plain-paged phased cells
                 # here: the SLO grid is already trace x cache x sched,
                 # and the serve workload owns the full kv_dtype cross —
                 # this axis just shows the quantized pool under
                 # multi-tenant SLO scoring (fp32 expands first, so the
                 # int8 cell's twin is cached)
                 "kv_dtype": ["fp32", "int8"],
                 # last axis -> phased expands before chunked for every
                 # cell, so the vs_phased ratio's twin is always cached
                 "sched": ["phased", "chunked"]},
                constraints=[lambda pt: pt["kv_dtype"] == "fp32"
                             or (pt["cache"] == "paged"
                                 and pt["sched"] == "phased")]),
    smoke={"trace": ["poisson", "shared_prefix", "long_prefill"]},
    tags=("serve", "smoke", "full"),
    result_columns=["arch", "trace", "cache", "sched", "kv_dtype",
                    "goodput",
                    "ttft_p99", "tpot_p99", "wh_per_slo_request",
                    "decode_tok_s", "prefix_hit_requests", "preemptions",
                    "ttft_p99_vs_paged", "wh_per_slo_vs_paged",
                    "ttft_p99_vs_phased", "goodput_vs_phased",
                    "speedup_vs_phased", "pool_bytes", "max_concurrency",
                    "speedup_vs_fp_kv", "kv_stream_prefix_agreement",
                    "trace_hash", "power_source"],
    primary_metric="goodput",
    # Tail quantiles from a SINGLE smoke run are scheduling-event-sized
    # (one GC pause or admission stall lands straight in p99): two
    # back-to-back clean runs differ 1.5-4x on ttft_p99/tpot_p99 while
    # throughput and energy hold within percent. They can't carry the
    # CI's blanket --rel-tol (which outranks the registry base — see
    # compare.effective_tolerance), so these stamps keep the columns
    # gated only against order-of-magnitude cliffs; the statistically
    # sound tail gate is scripts/check_ttft_gate.py (median-of-3 per
    # sched on the same host minutes apart). Throughput/energy columns
    # stay on the tight default.
    compare_tols={"ttft_p99": 4.0, "tpot_p99": 1.5,
                  "ttft_p99_vs_phased": 6.0},
)
def build(pt, ctx):
    """Multi-tenant traces x prefix caching, scored against SLOs."""
    c, engine = _engine(ctx, pt["arch"], pt["cache"],
                        n_blocks=POOL_BY_TRACE.get(pt["trace"]),
                        kv_dtype=pt["kv_dtype"])
    n = N_REQUESTS_SMOKE if ctx.smoke else N_REQUESTS
    cfg = preset_trace(pt["trace"], n_requests=n, vocab=c.vocab, seed=SEED)
    requests = generate_trace(cfg)

    # warm once per (engine, trace): compiles the trace's prefill
    # buckets and decode programs; repeat=2 lets a prefix engine
    # register on the first pass and compile every suffix-prefill
    # (bucket, depth) program on the second. The index is cleared
    # afterwards, so measured runs start cold either way.
    warmed = ctx.cache.setdefault("slo_warmed", set())
    wkey = (pt["arch"], pt["cache"], pt["trace"], pt["sched"],
            pt["kv_dtype"])
    if wkey not in warmed:
        engine.warmup(requests=requests,
                      repeat=2 if engine.prefix_cache else 1,
                      sched=pt["sched"])
        warmed.add(wkey)

    def run_cell():
        # same twice-run noise protocol as the serve workload: report
        # the steady-state second run, turn the pair's throughput
        # disagreement into the record's same-point noise figure. Each
        # measured run starts from a cold prefix index so the two runs
        # (and the promoted baseline) see identical hit sequences.
        def one_run():
            engine.reset_prefix_cache()
            return engine.serve(requests, policy="continuous",
                                sched=pt["sched"])

        first = one_run().summary
        out = one_run()
        s = out.summary
        pair = sorted((first.decode_tok_s, s.decode_tok_s))
        spread = ((pair[1] - pair[0]) / ((pair[0] + pair[1]) / 2)
                  if pair[1] > 0 else 0.0)
        ctx.last_measurement = Measurement(
            seconds=s.wall_s, energy_wh=s.attributed_wh,
            power_source=ctx.power_source, iters=2, warmup=0,
            rel_spread=spread)
        report = evaluate_slo(out.results, SLO_BY_TENANT,
                              default=SLO_TIGHT)
        metrics = {
            "goodput": report.goodput,
            "n_met": report.n_met,
            "n_requests": report.n_requests,
            "ttft_p50": report.ttft_p50_s,
            "ttft_p99": report.ttft_p99_s,
            "tpot_p50": report.tpot_p50_s,
            "tpot_p99": report.tpot_p99_s,
            "wh_per_slo_request": report.wh_per_slo_request,
            "n_tokens": s.n_tokens,
            "decode_tok_s": s.decode_tok_s,
            "wh_per_token": s.wh_per_token,
            "occupancy": s.mean_occupancy,
            "wall_s": s.wall_s,
            "seconds": s.wall_s,
            # full provenance: the trace is reproducible from its row
            "trace_seed": SEED,
            "trace_hash": cfg.config_hash(),
            # order-independent digest of every request's token stream:
            # equal across the sched and cache axes (same greedy argmax
            # path), so a quality-affecting scheduler bug shows up as a
            # hash mismatch in the results table even though the compare
            # gate (floats only) can't diff it
            "stream_hash": _stream_hash(out.results),
            "preemptions": engine.preemptions,
        }
        for name, sub in report.per_tenant.items():
            metrics[f"goodput_{name}"] = sub.goodput
        if engine.prefix_cache:
            for key, val in engine.prefix_stats.items():
                metrics[f"prefix_{key}"] = val
        # headline ratios against the twin cells: plain-paged (same
        # sched) and phased (same cache) — both expand earlier in the
        # Space, so they are already measured except under --points
        # structural pool capacity columns (every cell here is paged)
        metrics["pool_bytes"] = engine._paged.pool_bytes
        metrics["pool_bytes_fp"] = engine._paged.pool_bytes_fp
        metrics["max_concurrency"] = engine._paged.max_concurrency
        cells = ctx.cache.setdefault("serve_slo_cells", {})
        cell_key = (pt["arch"], pt["trace"])
        sub_key = (pt["cache"], pt["kv_dtype"], pt["sched"])
        cells.setdefault(cell_key, {})[sub_key] = metrics
        if pt["cache"] == "paged+prefix":
            base = cells[cell_key].get(
                ("paged", pt["kv_dtype"], pt["sched"]))
            if base is not None:   # absent only under --points filters
                metrics["ttft_p99_vs_paged"] = (
                    metrics["ttft_p99"] / max(base["ttft_p99"], 1e-9))
                metrics["wh_per_slo_vs_paged"] = (
                    metrics["wh_per_slo_request"]
                    / max(base["wh_per_slo_request"], 1e-12))
        if pt["sched"] == "chunked":
            base = cells[cell_key].get(
                (pt["cache"], pt["kv_dtype"], "phased"))
            if base is not None:   # absent only under --points filters
                metrics["ttft_p99_vs_phased"] = (
                    metrics["ttft_p99"] / max(base["ttft_p99"], 1e-9))
                metrics["goodput_vs_phased"] = (
                    metrics["goodput"] / max(base["goodput"], 1e-9))
                metrics["speedup_vs_phased"] = (
                    metrics["decode_tok_s"]
                    / max(base["decode_tok_s"], 1e-9))
        # int8 vs fp32 twin: perf/energy ratios + stream quality (same
        # protocol as the serve workload; streams keyed sans kv_dtype)
        streams = ctx.cache.setdefault("serve_slo_streams", {})
        skey = (pt["arch"], pt["trace"], pt["cache"], pt["sched"])
        my_streams = {r.rid: tuple(r.tokens) for r in out.results}
        if pt["kv_dtype"] == "fp32":
            streams[skey] = my_streams
        else:
            base = cells[cell_key].get((pt["cache"], "fp32", pt["sched"]))
            if base is not None:   # absent only under --points filters
                metrics["speedup_vs_fp_kv"] = (
                    metrics["decode_tok_s"]
                    / max(base["decode_tok_s"], 1e-9))
                metrics["wh_per_slo_vs_fp_kv"] = (
                    metrics["wh_per_slo_request"]
                    / max(base["wh_per_slo_request"], 1e-12))
            ref = streams.get(skey)
            if ref is not None:
                metrics["kv_stream_prefix_agreement"] = stream_agreement(
                    ref, my_streams)
        return metrics

    return {"serve_slo": run_cell}
