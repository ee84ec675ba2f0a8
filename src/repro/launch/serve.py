"""Serving CLI: fixed-batch generation or continuous-batching service.

Legacy fixed-batch run (one batched prefill + n decode steps):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --preset tiny --batch 4 --prompt-len 64 --gen 32

Continuous-batching service under synthetic Poisson load, with
energy-per-token accounting (see benchmarks/README.md):

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
      --mode continuous --slots 4 --requests 32 --rate 200
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.device import enable_compile_cache
from repro.data.synthetic import synthetic_tokens
from repro.models import lm
from repro.power.methods import select_power_methods
from repro.serve.engine import BatchedServer, ServeEngine
from repro.serve.requests import poisson_requests


def _run_batch(args, c, params):
    server = BatchedServer(c, params, max_len=args.gen + 1)
    prompts = jnp.asarray(synthetic_tokens(
        args.batch, args.prompt_len, c.vocab, args.seed)[:, :args.prompt_len])
    extras = {}
    if c.family == "vlm":
        extras["patch_embeds"] = jnp.zeros(
            (args.batch, c.n_patches, c.d_model), jnp.bfloat16)
    if c.family == "encdec":
        extras["enc_frames"] = jnp.zeros(
            (args.batch, c.enc_seq, c.d_model), jnp.bfloat16)

    res = server.generate(prompts, args.gen, extras)
    print(f"[serve] arch={c.name} batch={args.batch} "
          f"prefill={res.prefill_s * 1e3:.1f} ms "
          f"decode={res.decode_s * 1e3:.1f} ms "
          f"({res.decode_tokens_per_s:,.0f} tok/s decode)")
    return res


def _run_scheduled(args, c, params):
    methods, source = select_power_methods("auto")
    max_len = args.prompt_len + args.gen + 1
    if args.cache == "paged":   # paged pools allocate whole blocks
        max_len = -(-max_len // args.block_size) * args.block_size
    engine = ServeEngine(c, params, n_slots=args.slots, max_len=max_len,
                         cache=args.cache, block_size=args.block_size,
                         power_methods=methods)
    engine.warmup(prompt_len=args.prompt_len)
    reqs = poisson_requests(args.requests, args.rate, c.vocab,
                            prompt_len=args.prompt_len, seed=args.seed,
                            short=(max(args.gen // 4, 1), args.gen),
                            long=(max(args.gen // 4, 1), args.gen))
    out = engine.serve(reqs, policy=args.mode)
    s = out.summary
    print(f"[serve] arch={c.name} mode={args.mode} slots={args.slots} "
          f"rate={args.rate:g}/s power={source}")
    print(f"  {s.n_requests} requests, {s.n_tokens} tokens in "
          f"{s.wall_s:.2f} s -> {s.decode_tok_s:,.0f} tok/s "
          f"(cache={args.cache}, occupancy {s.mean_occupancy:.2f})")
    print(f"  ttft mean {s.mean_ttft_s * 1e3:.1f} ms / p95 "
          f"{s.p95_ttft_s * 1e3:.1f} ms")
    print(f"  energy {s.attributed_wh:.4f} Wh attributed "
          f"(+{s.overhead_wh:.4f} Wh overhead) -> "
          f"{s.wh_per_token * 1e3:.4f} mWh/token, "
          f"{s.wh_per_request * 1e3:.4f} mWh/request")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-117m")
    ap.add_argument("--preset", choices=["full", "tiny"], default="tiny")
    ap.add_argument("--mode", choices=["batch", "continuous", "fixed"],
                    default="batch",
                    help="batch = legacy one-shot generate; continuous/"
                         "fixed = scheduled serving under Poisson load")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache", choices=["slotted", "paged"],
                    default="slotted",
                    help="KV layout: dense per-slot rows or the paged "
                         "block-table pool (serve.cache.PagedKVCache)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV block size (tokens) for --cache paged")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rate <= 0:
        ap.error("--rate must be > 0 (Poisson arrival rate in req/s)")
    enable_compile_cache()

    c = get_config(args.arch)
    if args.preset == "tiny":
        c = c.reduced()
    params = lm.init(jax.random.key(args.seed), c)
    if args.mode == "batch":
        return _run_batch(args, c, params)
    return _run_scheduled(args, c, params)


if __name__ == "__main__":
    main()
