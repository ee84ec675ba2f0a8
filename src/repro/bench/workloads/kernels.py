"""Kernel microbenchmarks: Pallas vs XLA reference.

The Pallas kernels run compiled on a TPU and in interpret mode elsewhere.
Interpret mode executes the kernel body in Python — there the timing
column is a correctness-scale signal only; the real figure of merit on TPU is the
roofline delta (flash attention removes the O(S*T) score traffic from
the memory term). Power measurement is off: microsecond kernels are far
below the power sampling interval.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.bench.spec import workload
from repro.core.params import Space
from repro.kernels import ops

FLASH_SHAPES = {
    # case -> (batch, seq, heads, kv_heads, d_head)
    "flash_b1_s256": (1, 256, 4, 2, 64),
    "flash_b2_s512": (2, 512, 8, 8, 64),
}

#: paged prefill case: a 32-token chunk against 3 prefix pool blocks
#: per row (GQA 4q/2kv) — the serve chunked/suffix-prefill hot path
#: shape, scaled for interpret mode
PAGED_PREFILL_SHAPE = {"b": 2, "sq": 32, "h": 4, "kh": 2, "dh": 16,
                       "bs": 16, "npre": 3, "n_blocks": 8}


def _flash_inputs(case: str):
    b, s, h, kh, dh = FLASH_SHAPES[case]
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, dh), jnp.float32)
    return q, k, v


def _paged_prefill_inputs(quantized: bool):
    """(q, k_suffix, v_suffix, k_pool, v_pool, tables, k_scale, v_scale)
    with shuffled non-trivial block tables (block 0 left unused, like
    the serve pool's trash block)."""
    p = PAGED_PREFILL_SHAPE
    b, sq, h, kh, dh = p["b"], p["sq"], p["h"], p["kh"], p["dh"]
    bs, npre, nblk = p["bs"], p["npre"], p["n_blocks"]
    ks = jax.random.split(jax.random.key(1), 6)
    q = jax.random.normal(ks[0], (b, sq, h, dh), jnp.float32)
    k_suf = jax.random.normal(ks[1], (b, sq, kh, dh), jnp.float32)
    v_suf = jax.random.normal(ks[2], (b, sq, kh, dh), jnp.float32)
    k_pool = jax.random.normal(ks[3], (nblk, bs, kh, dh), jnp.float32)
    v_pool = jax.random.normal(ks[4], (nblk, bs, kh, dh), jnp.float32)
    tables = jax.random.permutation(
        ks[5], jnp.arange(1, nblk))[:b * npre].reshape(b, npre)
    k_scale = v_scale = None
    if quantized:
        def quant(pool):
            sc = jnp.max(jnp.abs(pool), axis=(1, 3)) / 127.0
            sc = jnp.where(sc > 0, sc, 1.0)
            codes = jnp.round(pool / sc[:, None, :, None])
            return jnp.clip(codes, -127, 127).astype(jnp.int8), sc
        k_pool, k_scale = quant(k_pool)
        v_pool, v_scale = quant(v_pool)
    return q, k_suf, v_suf, k_pool, v_pool, tables, k_scale, v_scale


@workload(
    "kernels",
    analog="Pallas kernel microbench (flash attention, rmsnorm, "
           "paged prefill)",
    space=Space({"case": ["flash_b1_s256", "flash_b2_s512", "rmsnorm",
                          "paged_prefill", "paged_prefill_int8"],
                 "impl": ["xla", "pallas"]}),
    smoke={"case": ["flash_b1_s256", "rmsnorm", "paged_prefill",
                    "paged_prefill_int8"]},
    tags=("kernels", "smoke", "full"),
    result_columns=["case", "impl", "us", "max_err", "interpret"],
    primary_metric="us",
    # interpret-mode microsecond timings on shared CPU hosts swing up to
    # ~10x run-to-run; absolute time is not gateable here (the docstring's
    # correctness-scale caveat). Cross-run compare still gates point
    # presence and error status — just not the timing deltas.
    compare_tols={"default": float("inf")},
)
def build(pt, ctx):
    """Pallas-vs-XLA kernel timing sweep."""
    case, impl = pt["case"], pt["impl"]
    # compiled on a TPU; off it, Pallas has only the interpreter
    interpret = impl == "pallas" and jax.default_backend() != "tpu"
    if case.startswith("paged_prefill"):
        quantized = case.endswith("int8")
        (q, k_suf, v_suf, k_pool, v_pool, tables, k_sc, v_sc) = ctx.memo(
            ("kernels_paged_prefill", quantized),
            lambda: _paged_prefill_inputs(quantized))

        def fn():
            return ops.paged_prefill_attention(
                q, k_suf, v_suf, k_pool, v_pool, tables, impl=impl,
                interpret=interpret, k_scale=k_sc, v_scale=v_sc)

        def run():
            m = ctx.measure(fn, iters=2 if interpret else 3, power=False)
            out = {"us": m.us, "seconds": m.seconds,
                   "interpret": int(interpret)}
            if impl == "pallas":
                # pallas rows carry their oracle delta so the
                # BENCH_kernels table is self-verifying: the xla rows
                # ARE paged_prefill_attention_ref
                oracle = ops.paged_prefill_attention(
                    q, k_suf, v_suf, k_pool, v_pool, tables, impl="xla",
                    k_scale=k_sc, v_scale=v_sc)
                out["max_err"] = float(jnp.max(jnp.abs(fn() - oracle)))
            return out

        return {"run": run}
    if case == "rmsnorm":
        x, sc = ctx.memo("kernels_rmsnorm", lambda: (
            jax.random.normal(jax.random.key(0), (512, 1024), jnp.float32),
            jnp.ones((1024,))))

        def fn():
            return ops.rmsnorm(x, sc, impl=impl, interpret=interpret)
    else:
        q, k, v = ctx.memo(("kernels_flash", case),
                           lambda: _flash_inputs(case))

        def fn():
            return ops.flash_attention(q, k, v, impl=impl,
                                       interpret=interpret)

    def run():
        m = ctx.measure(fn, iters=2 if interpret else 3, power=False)
        return {"us": m.us, "seconds": m.seconds,
                "interpret": int(interpret)}

    return {"run": run}
