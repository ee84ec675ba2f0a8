"""Quickstart: the end-to-end driver — train a GPT LM from scratch on
synthetic OSCAR-like data with the full pipeline (tokenizer -> indexed
dataset -> sharded loader -> train loop with checkpointing), measuring
throughput and energy CARAML-style.

  PYTHONPATH=src python examples/quickstart.py              # quick (tiny)
  PYTHONPATH=src python examples/quickstart.py --full-117m  # ~100M params,
      a few hundred steps (hours on this CPU host; minutes on one v5e chip)
"""
import argparse
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.metrics import tokens_per_s
from repro.data.indexed import IndexedDatasetReader, IndexedDatasetWriter
from repro.data.loader import ShardedLoader, lm_sample_fn
from repro.data.synthetic import synthetic_oscar_text
from repro.data.tokenizer import ByteFallbackTokenizer
from repro.models import lm
from repro.power.ctxmgr import get_power
from repro.power.methods import select_power_methods
from repro.train.loop import LoopConfig, train_loop
from repro.train.optimizer import OptConfig, opt_init
from repro.train.step import StepConfig, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full-117m", action="store_true",
                    help="train the real GPT-117M for a few hundred steps")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()

    if args.full_117m:
        c = get_config("gpt-117m")
        steps, gb, seq = args.steps or 300, 8, 256
    else:
        c = get_config("gpt-117m").reduced(d_model=128, n_layers=4, d_ff=512,
                                           n_heads=4, n_kv_heads=4, d_head=32,
                                           vocab=8192)
        steps, gb, seq = args.steps or 60, 8, 128

    print(f"== 1. data pipeline: synthetic OSCAR -> tokenizer -> "
          f"indexed dataset")
    docs = synthetic_oscar_text(2000, seed=0)
    tok = ByteFallbackTokenizer.train(docs, max_vocab=c.vocab)
    tmp = tempfile.mkdtemp()
    w = IndexedDatasetWriter(pathlib.Path(tmp) / "oscar")
    for d in docs:
        w.add_document(tok.encode(d))
    w.finalize(meta={"tokenizer": "byte-fallback", "docs": len(docs)})
    reader = IndexedDatasetReader(pathlib.Path(tmp) / "oscar")
    print(f"   {reader.n_documents} docs, {reader.n_tokens:,} tokens")

    print(f"== 2. model: {c.name} ({c.param_count() / 1e6:.1f}M params)")
    oc = OptConfig(lr=3e-4, warmup=max(steps // 20, 5), total_steps=steps)
    params = lm.init(jax.random.key(0), c)
    opt_state = opt_init(oc, params)
    step = jax.jit(make_train_step(c, oc, StepConfig()), donate_argnums=(0, 1))

    loader = ShardedLoader(lm_sample_fn(reader, seq), gb)

    def batches():
        for b in loader:
            yield {k: jnp.asarray(v) for k, v in b.items()}

    print(f"== 3. train {steps} steps (batch {gb} x seq {seq}) with "
          f"energy measurement")
    methods, source = select_power_methods("auto")
    cfg = LoopConfig(total_steps=steps, ckpt_every=max(steps // 2, 10),
                     ckpt_dir=str(pathlib.Path(tmp) / "ckpt"),
                     log_every=max(steps // 6, 5), seq_len=seq,
                     global_batch=gb)
    with get_power(methods, interval_ms=100) as scope:
        res = train_loop(step, params, opt_state, batches(), cfg)
    loader.close()
    wh = scope.total_energy_wh()
    n_tok = res.steps_run * gb * seq
    print(f"\n== results ==")
    print(f"loss: {res.losses[0]:.3f} -> {res.losses[-1]:.3f} "
          f"(decreased: {res.losses[-1] < res.losses[0]})")
    print(f"throughput: {res.tokens_per_s:,.0f} tokens/s")
    print(f"energy: {wh:.4f} Wh ({source}) -> "
          f"{n_tok / wh if wh else 0:,.0f} tokens/Wh")
    assert res.losses[-1] < res.losses[0], "training must reduce loss"


if __name__ == "__main__":
    main()
