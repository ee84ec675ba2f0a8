"""Bring-up check: the system's main paths on TPU at published widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

With no option the phases run in this order, in this one process:

  device   fail unless JAX's default backend is a TPU;
  train    gpt-800m (published widths), seq 2048, global batch 2, five
           steps through the step, optimizer and loop ``launch.train``
           uses; every loss finite;
  kernels  the Pallas paged decode and paged prefill kernels against
           ``kernels.ref`` at the serve phase's shapes, bf16 and int8
           pools;
  serve    llama3.2-3b (published widths, seeded random weights) through
           ``ServeEngine(cache="paged", sched="chunked")``: 16 requests
           whose prompts of several hundred tokens take several chunks,
           so chunked prefill reaches the paged prefill kernel; every
           request must complete with its full token count.

``--four-chips`` runs one phase alone: gpt-800m (published widths, depth
cut to 12 of 24 layers to fit) data-parallel over four chips (global
batch 8, two per chip) through ``shd.make_plan``, the ZeRO-1 shardings
and the bucketed ``grad_sync``, against a one-chip reference in the
same process (the same global batch as four microbatches of two on
device 0).

Any failing phase exits non-zero. The last line of standard output is a
JSON object naming the device, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.device import enable_compile_cache  # noqa: E402

TRAIN_ARCH, TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = "gpt-800m", 2048, 2, 5
SERVE_ARCH = "llama3.2-3b"
SERVE_SLOTS, SERVE_BLOCK = 16, 16
SERVE_MAX_LEN = 512                # 32 blocks a slot: one decode program
SERVE_CHUNK = 128                  # prefill chunk: several per prompt
SERVE_PROMPTS = (384, 448)         # 3 and 4 chunks; few distinct programs
SERVE_GEN = 32
FOUR_BATCH, FOUR_STEPS = 8, 3
#: depth of the four-chip phase's gpt-800m (widths published): at all 24
#: layers neither side fits 16 GB — the reference's fp32 accumulator over
#: 4 microbatches needs 10.6 + 8.9 GB, a dp4 chip's fp32 grad buckets
#: 3.8 + 13.8 GB (compile-only memory analysis for v5e). At 12 they need
#: 5.9 + 6.1 GB and 2.1 + 8.3 GB.
FOUR_LAYERS = 12
SEED = 0

#: Pallas kernels vs the float32 oracle, on identical bf16/int8 inputs.
#: The kernel's output is bf16 (relative rounding 2**-9) and the MXU may
#: take f32 operands in bf16 passes (logit error ~2**-8 on unit-scale
#: logits), so outputs of magnitude <= 1 may differ by a few 1e-3; a
#: wrong block, head, mask or scale moves them by O(0.1).
KERNEL_ATOL = 2e-2
#: dp4 vs one chip: the same arithmetic in a different reduction order
#: and fusion, in bf16 compute. Losses (~11 nats at init) agree to
#: ~1e-4 relative; 1e-3 relative leaves room for bf16 reassociation.
#: The gradient norm is the sharper check: a dropped or double-counted
#: shard changes it by tens of percent.
LOSS_RTOL = 1e-3
GNORM_RTOL = 1e-2


class PhaseFailed(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums XLA backend-compile durations (JAX's monitoring events; the
    tracing events nest, so summing them would count a nested function
    twice)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def hbm(dev=None) -> dict:
    stats = (dev or jax.devices()[0]).memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def per_device_bytes(tree) -> list:
    """Bytes of ``tree``'s shards held by each device, in device order."""
    held = {d: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return list(held.values())


def phase_device(want: int) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        raise PhaseFailed(f"device: JAX found platform {info['platform']!r}"
                          f" ({info['kind']}), not a TPU")
    if info["count"] < want:
        raise PhaseFailed(f"device: {want} chips needed, found "
                          f"{info['count']}")
    return info


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train_setup(c, steps: int, microbatches: int = 1):
    """The optimizer and jitted single-device step ``launch.train`` builds."""
    from repro.train.optimizer import OptConfig
    from repro.train.step import StepConfig, make_train_step
    oc = OptConfig(lr=3e-4, warmup=max(steps // 20, 5), total_steps=steps)
    step = jax.jit(make_train_step(c, oc, StepConfig(microbatches)),
                   donate_argnums=(0, 1))
    return oc, step


def init_state(c, oc):
    from repro.models import lm
    from repro.train.optimizer import opt_init
    params = lm.init(jax.random.key(SEED), c)
    return params, opt_init(oc, params)


def phase_train(clock: CompileClock) -> None:
    from repro.launch.train import make_data_fn
    from repro.train.loop import LoopConfig, train_loop
    c = get_config(TRAIN_ARCH)
    oc, step = train_setup(c, TRAIN_STEPS)
    params, opt_state = init_state(c, oc)
    data = make_data_fn(c, TRAIN_BATCH, TRAIN_SEQ, SEED)
    clock.lap()
    compiled = step.lower(params, opt_state, data(0)).compile()
    compile_s = clock.lap()
    step_s: list = []
    cfg = LoopConfig(total_steps=TRAIN_STEPS, ckpt_every=0, log_every=0,
                     seq_len=TRAIN_SEQ, global_batch=TRAIN_BATCH)
    res = train_loop(compiled, params, opt_state, data, cfg,
                     hooks=[lambda _s, _m, dt: step_s.append(dt)])
    log(f"[train] arch={c.name} params={c.param_count()} seq={TRAIN_SEQ} "
        f"global_batch={TRAIN_BATCH} compile_s={compile_s:.3f}")
    log(f"[train] step_s={[round(s, 4) for s in step_s]}")
    log(f"[train] losses={res.losses}")
    log(f"[train] hbm={hbm()}")
    if len(res.losses) != TRAIN_STEPS or not all(
            math.isfinite(x) for x in res.losses):
        raise PhaseFailed(f"train: losses {res.losses}")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _pool(rng, n_blocks, kh, dh, quantized):
    shape = (n_blocks, SERVE_BLOCK, kh, dh)
    if not quantized:
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16), None
    codes = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, (n_blocks, kh)) / 127,
                        jnp.float32)
    return codes, scale


def _max_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def phase_kernels() -> None:
    from repro.kernels import ref
    from repro.kernels.decode_attention import paged_decode_attention
    from repro.kernels.prefill_attention import paged_prefill_attention
    c = get_config(SERVE_ARCH)
    h, kh, dh = c.n_heads, c.n_kv_heads, c.d_head
    nb = SERVE_MAX_LEN // SERVE_BLOCK
    n_blocks = 1 + SERVE_SLOTS * nb
    npre = SERVE_PROMPTS[0] // SERVE_BLOCK - SERVE_CHUNK // SERVE_BLOCK
    rng = np.random.default_rng(SEED)
    perm = rng.permutation(np.arange(1, n_blocks))
    tables = jnp.asarray(perm[:SERVE_SLOTS * nb].reshape(SERVE_SLOTS, nb),
                         jnp.int32)
    lengths = jnp.asarray(rng.integers(1, SERVE_MAX_LEN + 1, SERVE_SLOTS),
                          jnp.int32)
    f32 = lambda x: None if x is None else x.astype(jnp.float32)  # noqa: E731
    worst = 0.0
    for quantized in (False, True):
        kp, ks = _pool(rng, n_blocks, kh, dh, quantized)
        vp, vs = _pool(rng, n_blocks, kh, dh, quantized)
        if quantized:
            # the oracle sees the same dequantized values in f32
            kp_ref = kp.astype(jnp.float32) * ks[:, None, :, None]
            vp_ref = vp.astype(jnp.float32) * vs[:, None, :, None]
        else:
            kp_ref, vp_ref = f32(kp), f32(vp)
        q = jnp.asarray(rng.normal(size=(SERVE_SLOTS, h, dh)), jnp.bfloat16)
        got = paged_decode_attention(q, kp, vp, tables, lengths,
                                     k_scale=ks, v_scale=vs)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_decode_attention_ref(f32(q), kp_ref, vp_ref,
                                                  tables, lengths)
        e_dec = _max_err(got, want)
        qs = jnp.asarray(rng.normal(size=(SERVE_SLOTS, SERVE_CHUNK, h, dh)),
                         jnp.bfloat16)
        k_suf = jnp.asarray(rng.normal(size=(SERVE_SLOTS, SERVE_CHUNK, kh,
                                              dh)), jnp.bfloat16)
        v_suf = jnp.asarray(rng.normal(size=k_suf.shape), jnp.bfloat16)
        pre = tables[:, :npre]
        got = paged_prefill_attention(qs, k_suf, v_suf, kp, vp, pre,
                                      k_scale=ks, v_scale=vs)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_prefill_attention_ref(
                f32(qs), f32(k_suf), f32(v_suf), kp_ref, vp_ref, pre)
        e_pre = _max_err(got, want)
        pool = "int8" if quantized else "bf16"
        log(f"[kernels] pool={pool} decode_max_err={e_dec:.3e} "
            f"prefill_max_err={e_pre:.3e} (tolerance {KERNEL_ATOL:g})")
        worst = max(worst, e_dec, e_pre)
    if not worst <= KERNEL_ATOL:      # also catches NaN
        raise PhaseFailed(f"kernels: max error {worst} > {KERNEL_ATOL}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def phase_serve(clock: CompileClock) -> None:
    from repro.models import lm
    from repro.serve.engine import ServeEngine
    from repro.serve.requests import Request
    c = get_config(SERVE_ARCH)
    params = lm.init(jax.random.key(SEED), c)
    engine = ServeEngine(c, params, n_slots=SERVE_SLOTS,
                         max_len=SERVE_MAX_LEN, cache="paged",
                         sched="chunked", block_size=SERVE_BLOCK,
                         chunk_tokens=SERVE_CHUNK)
    if engine.paged_impl != "pallas":
        raise PhaseFailed(f"serve: engine chose the {engine.paged_impl!r} "
                          f"paged attention, not the Pallas kernels")
    rng = np.random.default_rng(SEED)
    reqs = [Request(rid=i, prompt=rng.integers(0, c.vocab,
                                               SERVE_PROMPTS[i % 2], np.int32),
                    max_new_tokens=SERVE_GEN)
            for i in range(SERVE_SLOTS)]
    clock.lap()
    t0 = time.perf_counter()
    out = engine.serve(list(reqs), sched="chunked")
    wall = time.perf_counter() - t0
    compile_s = clock.lap()
    s = out.summary
    log(f"[serve] arch={c.name} params={c.param_count()} requests="
        f"{len(out.results)} tokens={s.n_tokens} prompts={SERVE_PROMPTS} "
        f"chunk={SERVE_CHUNK} wall_s={wall:.3f} compile_s={compile_s:.3f} "
        f"(the first serve compiles every program it reaches)")
    log(f"[serve] hbm={hbm()} pool_bytes={engine._paged.pool_bytes}")
    short = {r.rid: len(r.tokens) for r in out.results
             if len(r.tokens) != SERVE_GEN}
    if len(out.results) != len(reqs) or short:
        raise PhaseFailed(f"serve: {len(out.results)}/{len(reqs)} requests "
                          f"finished; short streams {short}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_four_chips(clock: CompileClock) -> None:
    from repro.bench.spec import Placement
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import mesh_for
    from repro.launch.train import make_data_fn
    from repro.parallel import grad_sync as gs
    from repro.parallel import sharding as shd
    from repro.train.step import StepConfig
    c = dataclasses.replace(get_config(TRAIN_ARCH), n_layers=FOUR_LAYERS)
    data = make_data_fn(c, FOUR_BATCH, TRAIN_SEQ, SEED)
    batches = [data(i) for i in range(FOUR_STEPS)]

    # one-chip reference: the global batch as 4 microbatches of 2
    oc, step = train_setup(c, FOUR_STEPS, microbatches=FOUR_BATCH // 2)
    params, opt_state = init_state(c, oc)
    ref_loss, ref_gnorm = [], []
    clock.lap()
    for b in batches:
        params, opt_state, m = step(params, opt_state, b)
        ref_loss.append(float(m["loss"]))
        ref_gnorm.append(float(m["gnorm"]))
    ref_compile = clock.lap()
    del params, opt_state, step
    log(f"[four] arch={c.name} n_layers={c.n_layers} seq={TRAIN_SEQ} "
        f"global_batch={FOUR_BATCH}")
    log(f"[four] reference 1 chip x {FOUR_BATCH // 2} microbatches: "
        f"losses={ref_loss} gnorm={ref_gnorm} compile_s={ref_compile:.3f}")

    # dp4: ZeRO-1 placement over the mesh, bucketed-grad-sync step
    params, opt_state = init_state(c, oc)
    plan = shd.make_plan(c, mesh_for(Placement.of("dp4")), ShapeConfig(
        "chip_smoke", TRAIN_SEQ, FOUR_BATCH, "train"))
    params, opt_state, psh, osh, _ = shd.shard_train_state(
        plan, params, opt_state, c)
    sync = gs.default_sync("fp32")
    step = jax.jit(gs.make_dp_train_step(c, oc, StepConfig(1), plan=plan,
                                         sync=sync),
                   out_shardings=(psh, osh, gs.sync_state_sharding(plan),
                                  None),
                   donate_argnums=(0, 1, 2))
    sync_state = gs.init_sync_state(plan, params, sync)
    state = per_device_bytes((params, opt_state))
    in_use = [hbm(d)["bytes_in_use"] for d in jax.devices()]
    dp_loss, dp_gnorm = [], []
    for b in batches:
        b = jax.device_put(b, {k: shd.batch_sharding(plan, v.shape)
                               for k, v in b.items()})
        params, opt_state, sync_state, m = step(params, opt_state,
                                                sync_state, b)
        dp_loss.append(float(m["loss"]))
        dp_gnorm.append(float(m["gnorm"]))
    dp_compile = clock.lap()
    log(f"[four] dp4 ({FOUR_BATCH // 4} per chip): losses={dp_loss} "
        f"gnorm={dp_gnorm} compile_s={dp_compile:.3f}")
    log(f"[four] after placement: state bytes per device={state} "
        f"bytes_in_use per device={in_use}")
    log(f"[four] hbm per device after steps="
        f"{[hbm(d) for d in jax.devices()]}")
    bad = [(i, a, b) for i, (a, b) in enumerate(zip(ref_loss, dp_loss))
           if not abs(a - b) <= LOSS_RTOL * abs(a)]
    bad += [(i, a, b) for i, (a, b) in enumerate(zip(ref_gnorm, dp_gnorm))
            if not abs(a - b) <= GNORM_RTOL * abs(a)]
    if bad:
        raise PhaseFailed(f"four: dp4 disagrees with the one-chip reference "
                          f"(step, reference, dp4): {bad}")
    # ZeRO-1 state: each chip holds a quarter of master/m/v plus a full
    # bf16 copy of the params, so no chip may hold much more than another
    for name, spread in (("state bytes", state), ("bytes_in_use", in_use)):
        if None not in spread and min(spread) < 0.5 * max(spread):
            raise PhaseFailed(f"four: {name} not spread over the chips: "
                              f"{spread}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dp4-vs-one-chip training phase")
    args = ap.parse_args(argv)
    enable_compile_cache()
    clock = CompileClock()
    try:
        info = phase_device(4 if args.four_chips else 1)
        if args.four_chips:
            phase_four_chips(clock)
        else:
            phase_train(clock)
            phase_kernels()
            phase_serve(clock)
    except PhaseFailed as e:
        print(f"FAILED {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
