"""Training CLI: the end-to-end driver.

  PYTHONPATH=src python -m repro.launch.train --arch gpt-117m --preset tiny \
      --steps 50 --ckpt-dir /tmp/ckpt

On a TPU host the same command runs the chosen config (``--preset
full`` for published widths); on a CPU container it runs reduced
configs end-to-end (the quickstart/benchmark path).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench.spec import Placement
from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.core.device import enable_compile_cache
from repro.core.manifest import write_manifest
from repro.data.loader import ShardedLoader, lm_sample_fn
from repro.data.synthetic import synthetic_tokens
from repro.faults.schedule import FaultSchedule, TRAIN_PRESETS
from repro.faults.supervisor import run_supervised
from repro.launch.mesh import mesh_for
from repro.models import lm
from repro.parallel import sharding as shd
from repro.train.loop import LoopConfig, train_loop
from repro.train.optimizer import OptConfig, opt_init
from repro.train.step import StepConfig, make_train_step


def make_data_iter(c, global_batch: int, seq_len: int, seed: int = 0,
                   batch_put=None):
    """``batch_put`` places each batch onto the active mesh (identity
    when training single-device)."""
    toks = synthetic_tokens(4096, seq_len, c.vocab, seed=seed)

    def sample(idx: int):
        row = toks[idx % toks.shape[0]]
        return {"tokens": row[:-1], "labels": row[1:]}

    loader = ShardedLoader(sample, global_batch)

    def gen():
        for batch in loader:
            out = {"tokens": jnp.asarray(batch["tokens"]),
                   "labels": jnp.asarray(batch["labels"])}
            if c.family == "vlm":
                out["patch_embeds"] = jnp.zeros(
                    (global_batch, c.n_patches, c.d_model), jnp.bfloat16)
            if c.family == "encdec":
                out["enc_frames"] = jnp.zeros(
                    (global_batch, c.enc_seq, c.d_model), jnp.bfloat16)
            yield batch_put(out) if batch_put is not None else out

    return gen()


def make_data_fn(c, global_batch: int, seq_len: int, seed: int = 0,
                 batch_put=None):
    """Step-indexed data: ``data(step) -> batch``, the resume-safe form.

    A fresh iterator restarts at sample 0 after a crash, silently
    desyncing the data stream from the checkpointed step counter —
    indexing by step keeps batch ``N`` identical whether the run reached
    step ``N`` directly or through three crash/resume cycles, which is
    what makes the resumed loss trace bit-equal to the uninterrupted
    one. Same sample indexing as :func:`make_data_iter` (rank 0 of 1),
    so the two forms produce identical batches at every step."""
    toks = synthetic_tokens(4096, seq_len, c.vocab, seed=seed)

    def sample(idx: int):
        row = toks[idx % toks.shape[0]]
        return {"tokens": row[:-1], "labels": row[1:]}

    def data(step: int):
        base = step * global_batch
        samples = [sample(base + j) for j in range(global_batch)]
        out = {"tokens": jnp.asarray(np.stack([s["tokens"]
                                               for s in samples])),
               "labels": jnp.asarray(np.stack([s["labels"]
                                               for s in samples]))}
        if c.family == "vlm":
            out["patch_embeds"] = jnp.zeros(
                (global_batch, c.n_patches, c.d_model), jnp.bfloat16)
        if c.family == "encdec":
            out["enc_frames"] = jnp.zeros(
                (global_batch, c.enc_seq, c.d_model), jnp.bfloat16)
        return batch_put(out) if batch_put is not None else out

    return data


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-117m")
    ap.add_argument("--preset", choices=["full", "tiny"], default="tiny",
                    help="tiny = reduced config for CPU end-to-end runs")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="inject a failure (fault-tolerance demo)")
    ap.add_argument("--fault-preset", default=None, choices=TRAIN_PRESETS,
                    help="seeded fault schedule; the run goes through the "
                         "bounded-restart supervisor (faults.supervisor)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--max-restarts", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--placement", default="dp1",
                    help="device mesh, e.g. dp4 or dp2tp2 — the same "
                         "Placement spelling the bench sweeps use")
    args = ap.parse_args(argv)
    enable_compile_cache()

    c = get_config(args.arch)
    if args.preset == "tiny":
        c = c.reduced()
    placement = Placement.of(args.placement)
    if placement.n_devices > jax.device_count():
        raise SystemExit(
            f"error: placement {placement.label} needs "
            f"{placement.n_devices} devices, process has "
            f"{jax.device_count()}; launch under the rendered Slurm "
            f"scripts (repro.launch.slurm) or set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={placement.n_devices}")
    print(f"[train] arch={c.name} params={c.param_count()/1e6:.1f}M "
          f"batch={args.global_batch} seq={args.seq_len} "
          f"placement={placement.label}")

    oc = OptConfig(lr=args.lr, warmup=max(args.steps // 20, 5),
                   total_steps=args.steps)
    sc = StepConfig(microbatches=args.microbatches)
    key = jax.random.key(args.seed)
    # state is rebuilt per supervisor attempt (the jitted step donates
    # its input buffers, so crashed state cannot be reused), then the
    # loop's auto-resume overwrites it from the checkpoint
    def init_state():
        p = lm.init(key, c)
        return p, opt_init(oc, p)

    params, opt_state = init_state()
    batch_put = None
    build_state = init_state
    if placement.n_devices > 1:
        # same placement path as the bench workloads: Plan from the mesh,
        # table-driven param/ZeRO-1 shardings, batch over the data axes —
        # including per-microbatch constraints (the (gb,)->(k, mb)
        # reshape loses the batch-axis sharding through GSPMD otherwise)
        plan = shd.make_plan(c, mesh_for(placement), ShapeConfig(
            "train_cli", args.seq_len, args.global_batch, "train"))
        params, opt_state, psh, osh, gsh = shd.shard_train_state(
            plan, params, opt_state, c)
        mbs = args.global_batch // max(args.microbatches, 1)
        bkeys = {"tokens": (mbs, args.seq_len),
                 "labels": (mbs, args.seq_len)}
        if c.family == "vlm":
            bkeys["patch_embeds"] = (mbs, c.n_patches, c.d_model)
        if c.family == "encdec":
            bkeys["enc_frames"] = (mbs, c.enc_seq, c.d_model)
        bsh = {k: shd.batch_sharding(plan, s) for k, s in bkeys.items()}
        # pin output shardings to the input placement — without this the
        # returned params' layout drifts from the placed inputs and every
        # call after the first recompiles (the dp-scaling collapse)
        step = jax.jit(make_train_step(c, oc, sc, grad_shardings=gsh,
                                       batch_shardings=bsh),
                       out_shardings=(psh, osh, None),
                       donate_argnums=(0, 1))

        def batch_put(batch):
            return jax.device_put(
                batch, {k: shd.batch_sharding(plan, v.shape)
                        for k, v in batch.items()})

        def build_state(p=psh, o=osh):
            fresh_p, fresh_o = init_state()
            return jax.device_put(fresh_p, p), jax.device_put(fresh_o, o)
    else:
        step = jax.jit(make_train_step(c, oc, sc), donate_argnums=(0, 1))

    data = make_data_fn(c, args.global_batch, args.seq_len, args.seed,
                        batch_put=batch_put)
    cfg = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                     ckpt_dir=args.ckpt_dir, log_every=10,
                     seq_len=args.seq_len, global_batch=args.global_batch)
    if args.fault_preset and args.fault_preset != "none":
        faults = FaultSchedule.from_preset(args.fault_preset,
                                           args.fault_seed, args.steps)
        print(f"[train] fault schedule {faults!r}")

        def run_once(hook):
            p, o = build_state()
            return train_loop(step, p, o, data, cfg, hooks=[hook],
                              faults=faults)

        sup = run_supervised(run_once, ckpt_dir=args.ckpt_dir,
                             max_restarts=args.max_restarts,
                             seed=args.fault_seed)
        res = sup.result
        print(f"[train] supervised: restarts={sup.restarts} "
              f"wasted_steps={sup.wasted_steps} "
              f"recovery_s={sup.recovery_s:.3f} "
              f"backoff_s={sup.backoff_s:.3f} "
              f"ckpt_fallbacks={sup.ckpt_fallbacks}")
    else:
        res = train_loop(step, params, opt_state, data, cfg,
                         fail_at_step=args.fail_at_step)
    print(f"[train] done: steps={res.steps_run} "
          f"first_loss={res.losses[0]:.4f} last_loss={res.losses[-1]:.4f} "
          f"tokens/s={res.tokens_per_s:,.0f} resumed_from={res.resumed_from}")
    return res


if __name__ == "__main__":
    main()
