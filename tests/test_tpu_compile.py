"""Compile the serve path's Pallas kernels for a described TPU v5e.

Interpret mode cannot see Mosaic's block-shape rules or its memory
limits; the TPU compiler, which is installed here, compiles for a chip
that is described and not attached. Each test lowers one paged kernel at
llama3.2-3b widths (H 24, Kh 8, Dh 128, block 16) for one chip of a
``v5e:2x2`` topology and checks that the compiled program holds the
kernel; one compiles the whole paged decode step of a two-layer stack at
those widths and checks that it updates the KV pool in place. Nothing
runs, so these say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.prefill_attention import paged_prefill_attention
from repro.models import lm

H, KH, DH, BS = 24, 8, 128, 16      # llama3.2-3b attention widths
SLOTS = 16                          # sequences in one serve step
MAX_BLOCKS = 64                     # table width: 1024 tokens per slot
N_BLOCKS = 1 + SLOTS * MAX_BLOCKS   # trash block + every slot full
CHUNK, NPRE = 128, 16               # a prefill chunk at 256 tokens in


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _pool_args(sharding, kv_dtype):
    pool = jax.ShapeDtypeStruct((N_BLOCKS, BS, KH, DH), kv_dtype,
                                sharding=sharding)
    if kv_dtype == jnp.int8:
        sc = jax.ShapeDtypeStruct((N_BLOCKS, KH), jnp.float32,
                                  sharding=sharding)
        return pool, pool, sc, sc
    return pool, pool, None, None


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_compiles_for_v5e(one_chip, kv_dtype):
    kp, vp, ks, vs = _pool_args(one_chip, kv_dtype)
    q = jax.ShapeDtypeStruct((SLOTS, H, DH), jnp.bfloat16, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((SLOTS, MAX_BLOCKS), jnp.int32,
                                  sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)

    def step(q, kp, vp, tables, lengths, ks, vs):
        return paged_decode_attention(q, kp, vp, tables, lengths,
                                      k_scale=ks, v_scale=vs)

    compiled = _compile(step, q, kp, vp, tables, lengths, ks, vs)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_prefill_compiles_for_v5e(one_chip, kv_dtype):
    kp, vp, ks, vs = _pool_args(one_chip, kv_dtype)
    q = jax.ShapeDtypeStruct((SLOTS, CHUNK, H, DH), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((SLOTS, CHUNK, KH, DH), jnp.bfloat16,
                              sharding=one_chip)
    tables = jax.ShapeDtypeStruct((SLOTS, NPRE), jnp.int32, sharding=one_chip)

    def step(q, k, v, kp, vp, tables, ks, vs):
        return paged_prefill_attention(q, k, v, kp, vp, tables,
                                       k_scale=ks, v_scale=vs)

    compiled = _compile(step, q, kv, kv, kp, vp, tables, ks, vs)
    assert "tpu_custom_call" in compiled.as_text()


def _pool_moves(hlo: str) -> list:
    """(computation, op, shape) of every dynamic-slice,
    dynamic-update-slice and copy in ``hlo`` whose shape carries the
    pool's block count, fused ones included."""
    found, comp = [], None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line != "}":
            comp = "ENTRY" if line.startswith("ENTRY") else line.split()[0]
        m = re.search(r"= \w+\[([\d,]*)\]\S* "
                      r"(dynamic-slice|dynamic-update-slice|copy)\(", line)
        if m and str(N_BLOCKS) in m.group(1).split(","):
            found.append((comp, m.group(2),
                          tuple(int(d) for d in m.group(1).split(","))))
    return found


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_step_updates_pool_in_place(one_chip, kv_dtype):
    """The engine's paged decode program (``lm.decode_step`` with block
    tables, the pool donated) over a two-layer stack: its temporaries
    stay below one layer's pool, and no layer's pool is sliced out,
    written back or copied whole. The one move left is an int8 pool's
    (L, n_blocks, Kh) f32 scales relaid out whole between the tiling XLA
    gives the leaf and the one the kernel reads (1/512 of the int8
    pool's bytes; for stacks under 8 layers XLA also does it inside the
    layer loop)."""
    n_layers = 2
    c = dataclasses.replace(get_config("llama3.2-3b"), n_layers=n_layers)
    assert (c.n_heads, c.n_kv_heads, c.d_head) == (H, KH, DH)
    shaped = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(shaped, lm.init_abstract(c))
    pool = jax.ShapeDtypeStruct((n_layers, N_BLOCKS, BS, KH, DH), kv_dtype,
                                sharding=one_chip)
    caches = {"slot0": {"k": pool, "v": pool}}
    if kv_dtype == jnp.int8:
        sc = jax.ShapeDtypeStruct((n_layers, N_BLOCKS, KH), jnp.float32,
                                  sharding=one_chip)
        caches["slot0"].update(k_scale=sc, v_scale=sc)
    i32 = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.int32, sharding=one_chip)

    def step(params, tok, caches, pos, tables):
        logits, caches = lm.decode_step(
            c, params, tok[:, None], caches, pos, block_tables=tables,
            n_kv_blocks=MAX_BLOCKS, paged_impl="pallas")
        return jnp.argmax(logits[:, -1], -1), caches

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        params, i32(SLOTS), caches, i32(SLOTS),
        i32(SLOTS, MAX_BLOCKS)).compile()
    one_layer = N_BLOCKS * BS * KH * DH * jnp.dtype(kv_dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    moves = _pool_moves(hlo)
    scale_relayout = ("copy", (n_layers, N_BLOCKS, KH))
    assert all(m[1:] == scale_relayout for m in moves), moves
    if kv_dtype == jnp.bfloat16:
        assert not moves
