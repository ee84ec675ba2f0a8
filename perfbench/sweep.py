"""Knee sweep of a serve cell: the highest offered rate the engine
sustains, found once, on the chip, to set the mix's fixed rate.

    python perfbench/sweep.py --workload <serve cell> --rates 0.5,1,1.5,2 \
        --seconds 40 [--out chiprun_out/sweep.jsonl]

One process builds and warms the engine once (as a run's set-up does),
then serves the mix's traffic at each offered rate in turn, each over a
window of ``--seconds``. For each rate it prints the requests, output
tokens per second, TTFT and TPOT percentiles, and the queue wait of the
first and the last fifth of the arrivals. The knee is the highest rate
at which the last fifth waits no longer than the first fifth beyond
their spread: past it the backlog grows through the window.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from perfbench import run as R  # noqa: E402
from perfbench import stats, traffic  # noqa: E402
from perfbench.model import reference_of  # noqa: E402


def fifths(res: list) -> tuple:
    """Queue waits (ms) of the first and the last fifth of the arrivals."""
    by = sorted(res, key=lambda r: r.arrival_s)
    k = max(len(by) // 5, 1)
    w = [(r.admitted_s - r.arrival_s) * 1e3 for r in by]
    return w[:k], w[-k:]


def sustained(first: list, last: list) -> bool:
    """The last fifth waits no longer than the first beyond its spread
    (1.5 interquartile ranges above its third quartile, and 50 ms)."""
    q1, q3 = (stats.percentile(first, 25), stats.percentile(first, 75))
    return stats.percentile(last, 50) <= q3 + 1.5 * (q3 - q1) + 50.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=9_000_000_011)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = R.read_json(R.ROOT / "BENCHMARK.json")
    files = R.cell_files(bench, args.workload)
    R.setup_jax()
    R.device_info(files["cell"]["chips"])
    sys.path.insert(0, str(R.ROOT / "src"))
    drv = R.load_module(R.HERE / "drivers" / "serve.py")
    ctx = R.Context(files, args.seed, args.seconds, False, 1)
    from repro.models import lm
    c = drv.model_config(ctx.cfg)
    params = reference_of(ctx.cfg).make_weights(lm.init_abstract(c),
                                                args.seed)
    engine = drv.make_engine(ctx, c, params)
    drv.warm(ctx, engine)
    sink = open(args.out, "a") if args.out else None
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(ctx.mix, arrivals={"kind": "poisson", "rate_per_s": rate})
        specs = traffic.generate(mix, args.seed, args.seconds,
                                 ctx.cfg["vocab_size"])
        t = time.perf_counter()
        out = engine.serve(drv.requests_of(specs))
        wall = time.perf_counter() - t
        res = out.results
        first, last = fifths(res)
        ttft = [(r.first_token_s - r.arrival_s) * 1e3 for r in res]
        tpot = [r.tpot_s * 1e3 for r in res if r.n_tokens > 1]
        n_out = sum(r.n_tokens for r in res)
        t0 = min(r.arrival_s for r in res)
        t1 = max(r.finish_s for r in res)
        ok = sustained(first, last)
        if ok:
            knee = rate
        row = {"workload": args.workload, "rate_per_s": rate,
               "requests": len(res), "wall_s": wall,
               "out_tokens_per_s": stats.rate(n_out, t0, t1),
               "ttft_p50_ms": stats.percentile(ttft, 50),
               "ttft_p90_ms": stats.percentile(ttft, 90),
               "tpot_p50_ms": stats.percentile(tpot, 50),
               "tpot_p90_ms": stats.percentile(tpot, 90),
               "wait_first_fifth_p50_ms": stats.percentile(first, 50),
               "wait_last_fifth_p50_ms": stats.percentile(last, 50),
               "sustained": ok, "preemptions": engine.preemptions}
        line = json.dumps(row)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    print(json.dumps({"workload": args.workload, "knee_rate_per_s": knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
