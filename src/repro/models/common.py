"""Shared layers: norms, activations, RoPE, MLP, embeddings, init helpers.

All models are pure functions ``apply(params, inputs) -> outputs`` over
nested-dict parameter pytrees. Initializers are plain functions of an rng
key so that ``jax.eval_shape`` can produce allocation-free abstract params
for the dry-run.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = Any  # nested dict of arrays


@jax.custom_jvp
def opt_barrier(x):
    """``optimization_barrier`` that differentiates as identity.

    jax.lax.optimization_barrier has no differentiation rule (through at
    least jax 0.4.x), so any barrier on the training forward path kills
    grad. The barrier only constrains XLA scheduling — mathematically it
    IS identity — so the tangent passes straight through unbarriered
    (a barriered tangent would need a transpose rule the primitive also
    lacks).
    """
    return jax.lax.optimization_barrier(x)


@opt_barrier.defjvp
def _opt_barrier_jvp(primals, tangents):
    (x,), (t,) = primals, tangents
    return opt_barrier(x), t


def dtype_of(c: ModelConfig):
    return jnp.dtype(c.dtype)


def param_dtype_of(c: ModelConfig):
    return jnp.dtype(c.param_dtype)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_shape, dtype) -> jax.Array:
    """Variance-scaling (fan-in) init, matching Megatron's scaled init."""
    shape = (in_dim, *out_shape) if isinstance(out_shape, tuple) else (in_dim, out_shape)
    std = 1.0 / jnp.sqrt(jnp.asarray(in_dim, jnp.float32))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(c: ModelConfig, dim: int | None = None) -> Params:
    dim = dim or c.d_model
    p = {"scale": jnp.ones((dim,), param_dtype_of(c))}
    if c.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), param_dtype_of(c))
    return p


def apply_norm(c: ModelConfig, p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    # barrier: stops XLA pulling this f32 cast back through the preceding
    # matmuls (it would convert whole stacked bf16 weights/caches to f32 and
    # hoist them out of the layer loop — measured 2x memory on 35B decode)
    x = opt_barrier(x)
    xf = x.astype(jnp.float32)
    if c.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)  # (Dh/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------


def mlp_init(key, c: ModelConfig, d_ff: int) -> Params:
    pd = param_dtype_of(c)
    ks = jax.random.split(key, 3)
    if c.act == "swiglu":
        p = {
            "wi_gate": dense_init(ks[0], c.d_model, d_ff, pd),
            "wi_up": dense_init(ks[1], c.d_model, d_ff, pd),
            "wo": dense_init(ks[2], d_ff, c.d_model, pd),
        }
    else:
        p = {
            "wi": dense_init(ks[0], c.d_model, d_ff, pd),
            "wo": dense_init(ks[1], d_ff, c.d_model, pd),
        }
    if c.mlp_bias:
        p["bi"] = jnp.zeros((d_ff,), pd)
        p["bo"] = jnp.zeros((c.d_model,), pd)
    return p


def apply_mlp(c: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if c.act == "swiglu":
        g = x @ p["wi_gate"]
        u = x @ p["wi_up"]
        if "bi" in p:
            g = g + p["bi"]
        h = jax.nn.silu(g) * u
    else:
        h = x @ p["wi"]
        if "bi" in p:
            h = h + p["bi"]
        h = jax.nn.gelu(h)
    y = h @ p["wo"]
    if "bo" in p:
        y = y + p["bo"]
    return y


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embedding_init(key, c: ModelConfig) -> Params:
    pd = param_dtype_of(c)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"tok": embed_init(k1, c.padded_vocab, c.d_model, pd)}
    if not c.tie_embeddings:
        p["head"] = embed_init(k2, c.padded_vocab, c.d_model, pd)
    if c.positions == "learned":
        p["pos"] = embed_init(k3, c.max_position, c.d_model, pd)
    return p


def embed_tokens(c: ModelConfig, p: Params, tokens: jax.Array,
                 positions: jax.Array | None = None) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0).astype(dtype_of(c))
    if c.embed_mult != 1.0:
        x = x * jnp.asarray(c.embed_mult, x.dtype)
    if c.positions == "learned" and positions is not None:
        # gather keeps memory linear even for very long positions tables
        x = x + jnp.take(p["pos"], positions, axis=0).astype(dtype_of(c))
    return x


def unembed(c: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    table = p["tok"] if c.tie_embeddings else p["head"]
    logits = jnp.einsum("...d,vd->...v", x, table)
    if c.logits_div != 1.0:
        logits = logits / jnp.asarray(c.logits_div, logits.dtype)
    if c.logits_softcap:
        logits = jnp.tanh(logits / c.logits_softcap) * c.logits_softcap
    # mask vocab padding so it never receives probability mass
    if c.padded_vocab != c.vocab:
        pad = c.padded_vocab - c.vocab
        mask = jnp.concatenate([
            jnp.zeros((c.vocab,), logits.dtype),
            jnp.full((pad,), jnp.finfo(jnp.float32).min, logits.dtype),
        ])
        logits = logits + mask
    return logits
