#!/usr/bin/env bash
# Tier-1 CI gate: collection must be clean, then the suite must pass,
# then the smoke benchmark suite must run end-to-end.
#
# Run from the repo root:  bash scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# 1. Collection errors fail fast and loudly (a module-level ImportError
#    in any test file must never be mistaken for a "skipped" test —
#    that is how the hypothesis import broke the seed suite unnoticed).
python -m pytest -q --collect-only >/dev/null

# 2. The tier-1 command from ROADMAP.md.
python -m pytest -x -q

# 3. Every smoke-tagged workload end-to-end through the unified CLI on
#    the deterministic synthetic power backend (multi-device workloads
#    get CPU devices configured in-process by the CLI, sized to the
#    largest placement in the selected sweeps).
#    The serve workload's smoke points cover BOTH KV layouts
#    (cache=slotted and cache=paged) on the XLA paged path; the
#    llm_train/resnet50/heatmap smoke spaces each include a dp-scaling
#    cell (placement=dp2 beside dp1), so the sharded execution path and
#    the derived scaling metrics (tok_s_per_device, scaling_efficiency,
#    wh_per_token_scaling) are exercised and baseline-gated on every run.
python -m repro.bench list
rm -rf artifacts/ci-bench   # no stale results from earlier local runs
python -m repro.bench run --tags smoke --power synthetic \
    --out artifacts/ci-bench

# 3a. The dp-scaling smoke cells must actually have recorded scaling
#     metrics — a silent stamping regression would otherwise disarm the
#     scaling gate while every raw-throughput cell stayed green — AND
#     multi-device llm_train cells must clear the scaling_efficiency
#     floor (ISSUE 6: dp2 collapsed to 0.10 via jit recompile churn;
#     the floor keeps scaling from silently inverting again). The
#     efficiency is emulation-aware (normalized by min(n_devices, host
#     cores) — runner._emulation_device_cap), so the floor is
#     meaningful even on a 1-core CI host faking N devices.
python - <<'EOF'
import json, sys
FLOOR = 0.6
recs = json.load(open("artifacts/ci-bench/llm_train/results.json"))["records"]
dp2 = [r for r in recs if r["point"].get("placement") == "dp2"
       and r["status"] == "ok"]
missing = [r["point"] for r in dp2
           if "scaling_efficiency" not in r["metrics"]
           or "wh_per_token_scaling" not in r["metrics"]]
if not dp2 or missing:
    sys.exit(f"dp-scaling smoke cell broken: dp2 cells={len(dp2)} "
             f"missing scaling metrics={missing}")
low = [(r["point"], r["metrics"]["scaling_efficiency"])
       for r in recs
       if r["status"] == "ok" and r.get("n_devices", 1) > 1
       and r["metrics"].get("scaling_efficiency", 1.0) < FLOOR]
if low:
    sys.exit(f"scaling_efficiency floor {FLOOR} violated: {low}")
effs = [round(r["metrics"]["scaling_efficiency"], 3) for r in dp2]
print(f"dp-scaling smoke: {len(dp2)} dp2 cell(s), "
      f"scaling_efficiency={effs} (floor {FLOOR})")
EOF

# 3b. Prefix-caching effectiveness gate (ISSUE 7 acceptance): the
#     serve_slo smoke run must show the shared_prefix trace's
#     cache=paged+prefix cell actually hitting the prefix index AND
#     measurably beating the plain paged twin on TTFT p99 and
#     Wh-per-SLO-met-request. Thresholds sit well above the measured
#     ratios (ttft ~0.34x, wh ~0.77x) so only a broken prefix path —
#     not noise — trips them. Token-stream equality is the pytest
#     suite's job (tests/test_prefix_cache.py); this gate covers the
#     performance half of the contract.
python - <<'EOF'
import json, sys
recs = json.load(open("artifacts/ci-bench/serve_slo/results.json"))["records"]
# the sched axis doubles every (trace, cache) cell: the prefix gate
# compares the phased twins so the ratio isolates caching alone
cells = {(r["point"]["trace"], r["point"]["cache"]): r["metrics"]
         for r in recs
         if r["status"] == "ok" and r["point"].get("sched") == "phased"}
base = cells.get(("shared_prefix", "paged"))
pref = cells.get(("shared_prefix", "paged+prefix"))
if base is None or pref is None:
    sys.exit(f"serve_slo shared_prefix cells missing: {sorted(cells)}")
if pref.get("prefix_hit_requests", 0) <= 0:
    sys.exit("prefix cache never hit on the shared_prefix trace")
ttft_ratio = pref["ttft_p99"] / max(base["ttft_p99"], 1e-12)
wh_ratio = pref["wh_per_slo_request"] / max(base["wh_per_slo_request"], 1e-12)
if ttft_ratio > 0.8:
    sys.exit(f"prefix caching stopped helping TTFT p99: ratio {ttft_ratio:.3f}")
if wh_ratio > 0.95:
    sys.exit(f"prefix caching stopped helping Wh/SLO-request: "
             f"ratio {wh_ratio:.3f}")
print(f"prefix-cache gate: hits={pref['prefix_hit_requests']} "
      f"ttft_p99 ratio={ttft_ratio:.3f} wh/slo ratio={wh_ratio:.3f}")
EOF

# 3d. TTFT-cliff gate (ISSUE 8 acceptance): on the tight-pool
#     long_prefill trace, the chunked scheduler must hold its median
#     ttft_p99 at <= 0.7x phased with goodput no worse, token streams
#     bit-identical across every run of both schedulers, and real
#     preemptions recorded (zero would mean the oversubscribed regime
#     went slack and the gate is measuring nothing). Median-of-3 per
#     sched: single-run tail quantiles are too noisy to gate on a
#     shared host — the serve_slo workload rows still record the
#     single-run vs_phased ratios with a generous compare tolerance.
python scripts/check_ttft_gate.py

# 3e. Resilience gate (ISSUE 9 acceptance): the crash_mid smoke cell
#     must complete through the bounded-restart supervisor — crash
#     mid-run, backoff, resume from the newest valid checkpoint — with
#     the recompute bounded by the checkpoint cadence and the resumed
#     loss trace element-equal to the fault-free twin's (loss_bitmatch:
#     resume restored the real state and the step-indexed data stream
#     stayed aligned). The none-preset twin cell must not restart at
#     all, and every cell carries its schedule_hash stamp.
python - <<'EOF'
import json, math, sys
recs = json.load(open("artifacts/ci-bench/resilience/results.json"))["records"]
cells = {r["point"]["fault_preset"]: r for r in recs if r["status"] == "ok"}
crash, none = cells.get("crash_mid"), cells.get("none")
if crash is None or none is None:
    sys.exit(f"resilience smoke cells missing: have {sorted(cells)}")
m, ck = crash["metrics"], int(crash["point"]["ckpt_every"])
if m["final_step"] != 30:
    sys.exit(f"crash_mid cell never finished: final_step={m['final_step']}")
if m["restarts"] < 1:
    sys.exit("crash_mid cell never crashed — the schedule went dead")
bound = ck * m["tokens_per_step"]
if m["wasted_tokens"] > bound:
    sys.exit(f"wasted_tokens {m['wasted_tokens']} exceeds ckpt cadence "
             f"bound {bound} — resume skipped a usable checkpoint")
if m["loss_bitmatch"] != 1.0:
    sys.exit("resumed loss trace diverged from the fault-free twin")
if not math.isfinite(m["wh_overhead_resilience"]):
    sys.exit(f"wh_overhead_resilience not finite: "
             f"{m['wh_overhead_resilience']}")
if none["metrics"]["restarts"] != 0 or none["metrics"]["loss_bitmatch"] != 1.0:
    sys.exit(f"fault-free twin cell dirty: {none['metrics']}")
missing_hash = [p for p, r in cells.items()
                if not r["metrics"].get("schedule_hash")]
if missing_hash:
    sys.exit(f"cells without a schedule_hash stamp: {missing_hash}")
print(f"resilience gate: restarts={m['restarts']} "
      f"wasted_tokens={m['wasted_tokens']}<={bound} "
      f"recovery_s={m['recovery_s']:.3f} loss_bitmatch=1 "
      f"wh_overhead={m['wh_overhead_resilience']:.4f}")
EOF

# 3g. int8 KV-block gate (ISSUE 10 acceptance): every paged continuous
#     fp32/int8 twin pair in the smoke run must show (a) the quantized
#     pool at <= 0.55x the fp32 bytes for the SAME block count (int8
#     blocks + f32 per-block-per-head scales against bf16 blocks —
#     measured 0.508), (b) max_concurrency at least doubled at the fp
#     byte budget, (c) energy per token no worse than ~parity (measured
#     ratio 0.92; the 1.10 ceiling only absorbs single-run CPU wobble,
#     a real int8-path slowdown lands far above it), and (d) greedy
#     token streams tracking the fp32 twin's (mean longest-common-prefix
#     fraction >= 0.70; measured 0.85 — quantization flips some argmax
#     ties mid-stream, but a kernel/scale bug collapses agreement toward
#     0 because streams diverge at the first token).
python - <<'EOF'
import json, sys
recs = json.load(open("artifacts/ci-bench/serve/results.json"))["records"]
cells = {}
for r in recs:
    p = r["point"]
    if (r["status"] == "ok" and p.get("cache") == "paged"
            and p.get("policy") == "continuous"):
        key = (p["slots"], p["rate_hz"], p["sched"])
        cells.setdefault(key, {})[p["kv_dtype"]] = r["metrics"]
pairs = {k: v for k, v in cells.items() if "fp32" in v and "int8" in v}
if not pairs:
    sys.exit(f"no fp32/int8 paged-continuous twin cells: {sorted(cells)}")
for key, v in sorted(pairs.items()):
    fp, i8 = v["fp32"], v["int8"]
    pool = i8["pool_bytes"] / max(fp["pool_bytes"], 1)
    if pool > 0.55:
        sys.exit(f"{key}: int8 pool_bytes ratio {pool:.3f} > 0.55")
    if i8["max_concurrency"] < 2 * fp["max_concurrency"]:
        sys.exit(f"{key}: int8 max_concurrency {i8['max_concurrency']} "
                 f"< 2x fp32 {fp['max_concurrency']}")
    wh = i8["wh_per_token"] / max(fp["wh_per_token"], 1e-12)
    if wh > 1.10:
        sys.exit(f"{key}: int8 wh_per_token ratio {wh:.3f} > 1.10")
    agree = i8.get("kv_stream_prefix_agreement")
    if agree is None or agree < 0.70:
        sys.exit(f"{key}: kv_stream_prefix_agreement {agree} < 0.70")
    if "speedup_vs_fp_kv" not in i8:
        sys.exit(f"{key}: int8 cell missing speedup_vs_fp_kv")
    print(f"int8 gate {key}: pool={pool:.3f} "
          f"conc={fp['max_concurrency']}->{i8['max_concurrency']} "
          f"wh_ratio={wh:.3f} agree={agree:.3f} "
          f"speedup={i8['speedup_vs_fp_kv']:.3f}")
EOF

# 4. Regression gate: the smoke run just produced must not be slower or
#    hungrier than the committed baselines beyond tolerance. The base
#    tolerance is 0.3 (was 0.45, was 0.6): every workload stamps
#    same-point measure_split noise (rel_std 0.03-0.15) and the compare
#    engine widens per point by noise_k * rel_std, so the blanket only
#    needs to cover systematic host drift, not per-point wobble;
#    workloads that genuinely can't hold 0.3 carry their own
#    compare_tols. `make bench-compare` runs the tight default gate
#    locally. Refresh the store after an intentional perf change with
#    `make bench-promote` and commit artifacts/bench/baselines/.
python -m repro.bench compare artifacts/bench/baselines artifacts/ci-bench \
    --fail-on-regression --fail-on-missing --rel-tol default=0.3 \
    --report-out artifacts/ci-bench/compare-report.md
