"""Serving workload: continuous vs fixed batching x slotted vs paged KV.

The MLPerf-Power/CARAML serving point: drive the ServeEngine with a
seeded synthetic Poisson arrival process and a bimodal short/long token
mix, per (slots x rate x cache x policy) cell:

  decode_tok_s    useful generated tokens per wall second
  ttft_s          mean time-to-first-token (includes queueing)
  wh_per_token    energy per generated token (attributed per request)
  wh_per_request  energy per served request
  occupancy       mean decode-step batch occupancy (active/n_slots)
  speedup_vs_fixed    continuous/fixed tokens/s for the same cell
  speedup_vs_slotted  paged/slotted tokens/s for the same cell

Axes isolate the wins separately: ``policy`` flips only admission
(iteration-level refill vs batch-fill barrier) on identical programs, so
``speedup_vs_fixed`` is the pure scheduling gain; ``cache`` flips only
the KV layout (dense ``max_len`` rows vs ``serve.cache.PagedKVCache``
block tables whose decode attention walks just the blocks a slot owns),
so ``speedup_vs_slotted`` is the pure memory-layout gain; ``sched``
flips only the prefill schedule (whole-prompt-at-admission vs
block-aligned ``chunk_tokens`` slices interleaved with decode, with
block-granular preemption backing decode growth), so
``speedup_vs_phased`` is the pure iteration-level-scheduling delta —
~1.0 here, where every prompt fits one chunk; the serve_slo workload's
``long_prefill`` trace is where it separates. Chunked requires the
paged cache (the Space constraint drops chunked x slotted cells
outright, so the grid carries no skip records). ``kv_dtype`` flips the
paged pool to int8 blocks with per-block-per-head scales (continuous
paged cells only): ``pool_bytes``/``max_concurrency`` carry the
capacity win (same block count at ~half the bytes, so ~2x the
worst-case-length requests per fp byte budget), ``speedup_vs_fp_kv``
and ``wh_per_token_vs_fp_kv`` the perf/energy deltas, and
``kv_stream_prefix_agreement`` the token-stream quality vs the fp32
twin's greedy streams (1.0 on the reduced config — quantization noise
below the argmax margin). All cells share the
batched-prefill + fused-decode serve loop. The paged cells run the
Pallas paged-attention kernels on a TPU and their XLA reference
elsewhere (``serve.engine.paged_kernel``). Energy comes from the
runner-selected power backend, labeled ``power_source``.
"""
from __future__ import annotations

import jax

from repro.bench.context import Measurement
from repro.bench.spec import workload
from repro.configs import get_config
from repro.core.params import Space
from repro.models import lm
from repro.serve.engine import ServeEngine
from repro.serve.requests import poisson_requests

PROMPT_LEN = 8          # fixed: one prefill trace for the whole sweep
MAX_LEN = 96            # slot capacity (multiple of reduced ssm_chunk)
BLOCK_SIZE = 16         # paged KV block (tokens); 6 blocks per full slot
N_REQUESTS = 48
N_REQUESTS_SMOKE = 64   # enough that the drain tail amortizes away
SEED = 0


def _engine(ctx, arch: str, n_slots: int, cache: str,
            kv_dtype: str = "fp32") -> ServeEngine:
    def make():
        c = get_config(arch).reduced()
        params = lm.init(jax.random.key(SEED), c)
        engine = ServeEngine(c, params, n_slots=n_slots, max_len=MAX_LEN,
                             cache=cache, block_size=BLOCK_SIZE,
                             kv_dtype=kv_dtype,
                             power_methods=ctx.power_methods)
        # warmup: compile every serve program (prompt-bucket prefill,
        # insert, each paged gather bucket) outside any measured cell —
        # the first serve() otherwise charges XLA compilation to the
        # first policy's wall clock and energy
        engine.warmup(prompt_len=PROMPT_LEN)
        return c, engine

    return ctx.memo(("serve", arch, n_slots, cache, kv_dtype), make)


def stream_agreement(ref_streams: dict, cur_streams: dict) -> float:
    """Mean longest-common-prefix fraction of per-request token streams
    against the reference run: 1.0 = bit-identical generation, lower =
    quantization (or a scheduler bug) steered greedy decoding off the
    reference trajectory at 1-lcp/len of the way through an average
    request. Keyed by rid so completion-order differences don't count."""
    fracs = []
    for rid, rt in ref_streams.items():
        ct = cur_streams.get(rid)
        if ct is None:
            continue
        lcp = 0
        for a, b in zip(rt, ct):
            if a != b:
                break
            lcp += 1
        fracs.append(lcp / max(len(rt), len(ct), 1))
    return sum(fracs) / max(len(fracs), 1)


@workload(
    "serve",
    analog="serving: continuous batching + Wh/token (MLPerf-Power style)",
    space=Space({"arch": ["llama3.2-3b"], "slots": [4, 8],
                 "rate_hz": [100.0, 400.0],
                 "cache": ["slotted", "paged"],
                 "policy": ["fixed", "continuous"],
                 # kv_dtype expands before sched, so an int8 cell's fp32
                 # twin (same sched) is always measured first; int8 only
                 # exists for the paged continuous cells (quantized
                 # blocks live in the pool, and the capacity win is a
                 # continuous-batching story)
                 "kv_dtype": ["fp32", "int8"],
                 # last axis -> phased expands before chunked for every
                 # cell, so the vs_phased ratio's twin is always cached
                 "sched": ["phased", "chunked"]},
                constraints=[lambda pt: not (pt["sched"] == "chunked"
                                             and pt["cache"] == "slotted"),
                             lambda pt: pt["kv_dtype"] == "fp32"
                             or (pt["cache"] == "paged"
                                 and pt["policy"] == "continuous")]),
    smoke={"slots": [4], "rate_hz": [300.0]},
    tags=("serve", "smoke", "full"),
    result_columns=["arch", "cache", "policy", "sched", "kv_dtype",
                    "slots", "rate_hz",
                    "n_tokens", "decode_tok_s", "ttft_s", "occupancy",
                    "wh_per_token", "wh_per_request", "speedup_vs_fixed",
                    "speedup_vs_slotted", "speedup_vs_phased",
                    "pool_bytes", "max_concurrency", "speedup_vs_fp_kv",
                    "kv_stream_prefix_agreement", "power_source"],
    primary_metric="decode_tok_s",
    # mean TTFT includes queueing, and at fixed-policy 300 Hz the queue
    # depth is set by host speed during admission — run-to-run swings of
    # ~1.5x on an otherwise-unchanged build. Wide stamp catches only a
    # real cliff; throughput/energy columns stay on the tight default.
    compare_tols={"ttft_s": 2.0},
)
def build(pt, ctx):
    """Continuous vs fixed batching, slotted vs paged KV, Poisson load."""
    c, engine = _engine(ctx, pt["arch"], pt["slots"], pt["cache"],
                        pt["kv_dtype"])
    n = N_REQUESTS_SMOKE if ctx.smoke else N_REQUESTS
    requests = poisson_requests(n, pt["rate_hz"], c.vocab,
                                prompt_len=PROMPT_LEN, seed=SEED)

    def run_cell():
        # two full repetitions of the cell: the second (steady-state) run
        # is reported, and the pair's throughput disagreement becomes the
        # record's same-point noise figure (source=measure_split) — the
        # serve engine orchestrates its own timing, so without this the
        # runner would fall back to the straggler watchdog's cross-point
        # spread, which mixes multi-second fixed cells with sub-second
        # continuous cells and saturates the compare-gate tolerance.
        first = engine.serve(requests, policy=pt["policy"],
                             sched=pt["sched"]).summary
        out = engine.serve(requests, policy=pt["policy"],
                           sched=pt["sched"])
        s = out.summary
        pair = sorted((first.decode_tok_s, s.decode_tok_s))
        spread = ((pair[1] - pair[0]) / ((pair[0] + pair[1]) / 2)
                  if pair[1] > 0 else 0.0)
        ctx.last_measurement = Measurement(
            seconds=s.wall_s, energy_wh=s.attributed_wh,
            power_source=ctx.power_source, iters=2, warmup=0,
            rel_spread=spread)
        metrics = {
            "n_requests": s.n_requests,
            "n_tokens": s.n_tokens,
            "decode_tok_s": s.decode_tok_s,
            "ttft_s": s.mean_ttft_s,
            "p95_ttft_s": s.p95_ttft_s,
            "occupancy": s.mean_occupancy,
            "wh_per_token": s.wh_per_token,
            "wh_per_request": s.wh_per_request,
            "overhead_wh": s.overhead_wh,
            "wall_s": s.wall_s,
            "seconds": s.wall_s,
            # the arrival-process seed rides along so a record is fully
            # reproducible from its own row (same contract as the
            # serve_slo workload's trace_seed/trace_hash stamp)
            "request_seed": SEED,
        }
        # headline ratios. The twin cells are normally already cached
        # (the Space expands cache=slotted before paged and policy=fixed
        # before continuous), but a filtered run (--points ...) still
        # gets speedup_vs_fixed: that baseline is measured on demand.
        cells = ctx.cache.setdefault("serve_cells", {})
        cell_key = (pt["arch"], pt["slots"], pt["rate_hz"], pt["cache"],
                    pt["kv_dtype"], pt["sched"])
        cells.setdefault(cell_key, {})[pt["policy"]] = metrics
        if pt["cache"] == "paged":
            # structural capacity story: actual pool bytes (int8 blocks +
            # scales when quantized), what the same block count costs at
            # the native KV dtype, and how many worst-case-length
            # requests fit the fp byte budget (see PagedKVCache)
            metrics["pool_bytes"] = engine._paged.pool_bytes
            metrics["pool_bytes_fp"] = engine._paged.pool_bytes_fp
            metrics["max_concurrency"] = engine._paged.max_concurrency
        # int8 vs fp32 twin: throughput/energy ratios plus the
        # token-stream quality figure (streams keyed without kv_dtype so
        # the int8 cell finds its fp32 reference run)
        streams = ctx.cache.setdefault("serve_streams", {})
        skey = (pt["arch"], pt["slots"], pt["rate_hz"], pt["cache"],
                pt["policy"], pt["sched"])
        my_streams = {r.rid: tuple(r.tokens) for r in out.results}
        if pt["kv_dtype"] == "fp32":
            streams[skey] = my_streams
        else:
            fp_key = cell_key[:4] + ("fp32",) + cell_key[5:]
            fp = cells.get(fp_key, {}).get(pt["policy"])
            if fp is not None:   # absent only under --points filters
                metrics["speedup_vs_fp_kv"] = (
                    metrics["decode_tok_s"]
                    / max(fp["decode_tok_s"], 1e-9))
                metrics["wh_per_token_vs_fp_kv"] = (
                    metrics["wh_per_token"]
                    / max(fp["wh_per_token"], 1e-12))
            ref = streams.get(skey)
            if ref is not None:
                metrics["kv_stream_prefix_agreement"] = stream_agreement(
                    ref, my_streams)
        if pt["policy"] == "continuous":
            fixed = cells[cell_key].get("fixed")
            if fixed is None:
                baseline = engine.serve(requests, policy="fixed",
                                        sched=pt["sched"])
                fixed = {"decode_tok_s": baseline.summary.decode_tok_s}
                cells[cell_key]["fixed"] = fixed
            metrics["speedup_vs_fixed"] = (
                metrics["decode_tok_s"] / max(fixed["decode_tok_s"], 1e-9))
        if pt["cache"] == "paged":
            # slotted twin is always fp32 (no quantized slotted cells)
            slot_key = (pt["arch"], pt["slots"], pt["rate_hz"], "slotted",
                        "fp32", pt["sched"])
            slotted = cells.get(slot_key, {}).get(pt["policy"])
            if slotted is not None:   # absent for chunked (no slotted twin)
                metrics["speedup_vs_slotted"] = (
                    metrics["decode_tok_s"]
                    / max(slotted["decode_tok_s"], 1e-9))
        if pt["sched"] == "chunked":
            phase_key = (pt["arch"], pt["slots"], pt["rate_hz"],
                         pt["cache"], pt["kv_dtype"], "phased")
            phased = cells.get(phase_key, {}).get(pt["policy"])
            if phased is not None:   # absent only under --points filters
                metrics["speedup_vs_phased"] = (
                    metrics["decode_tok_s"]
                    / max(phased["decode_tok_s"], 1e-9))
        return metrics

    return {"serve": run_cell}
