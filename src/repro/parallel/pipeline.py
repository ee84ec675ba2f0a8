"""Pipeline parallelism via shard_map + collective_permute (1F1B-style).

The paper's Graphcore case trains GPT-117M with its layers pipelined over
4 IPUs (the only way it fits in per-core SRAM); the 13B/175B JUBE configs
pipeline over nodes. On TPU we map the pattern onto a mesh "stage" axis —
for multi-pod, the natural choice is pod = stage (the DCN link carries
only the (B, S, D) activation handoff once per microbatch, the cheapest
possible cross-pod pattern).

Implementation: GPipe/1F1B microbatch schedule expressed as a rotation
loop. Each device holds n_layers/n_stages contiguous layers; microbatch i
enters stage 0, activations are collective_permuted to the next stage
each tick. Forward schedule shown; the backward runs through jax.grad of
the whole rotated loop (activations rematerialized per microbatch).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


Params = Any


def stage_params_split(params_stacked, n_stages: int):
    """Split scan-stacked layer params (L, ...) into (n_stages, L/S, ...)."""
    def split(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree.map(split, params_stacked)


def pipeline_forward(mesh: Mesh, stage_axis: str, layer_fn: Callable,
                     stage_params, x_microbatches: jax.Array):
    """Run microbatches through pipeline stages.

    layer_fn(params_for_stage, x) -> x, applied per stage.
    stage_params: pytree with leading dim = n_stages (sharded over
    ``stage_axis``); x_microbatches: (n_mb, mb, S, D) — each microbatch is
    replicated (or data-sharded on its own axes) across stages.

    Returns (n_mb, mb, S, D) outputs. Uses the rotation schedule: at tick
    t, stage s processes microbatch (t - s); a collective_permute hands
    activations to stage s+1. Total ticks = n_mb + n_stages - 1 (the
    pipeline bubble the paper observes on the IPU is exactly the
    (n_stages-1)/(n_mb+n_stages-1) idle fraction).
    """
    n_stages = mesh.shape[stage_axis]
    n_mb = x_microbatches.shape[0]
    mb_shape = x_microbatches.shape[1:]

    def per_stage(params, xs):
        # params: (1, L/S, ...) local stage slice; xs: (n_mb, ...) local
        params = jax.tree.map(lambda p: p[0], params)
        stage_id = jax.lax.axis_index(stage_axis)
        n_ticks = n_mb + n_stages - 1
        buf = jnp.zeros(mb_shape, xs.dtype)  # current activation
        outs = jnp.zeros((n_mb, *mb_shape), xs.dtype)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            mb_idx = jnp.clip(t, 0, n_mb - 1)
            incoming = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                    keepdims=False)
            buf = jnp.where(stage_id == 0,
                            jnp.where(t < n_mb, incoming, buf), buf)
            # every stage runs its layers on its current buffer
            y = layer_fn(params, buf)
            # emit from the last stage: microbatch (t - n_stages + 1)
            out_idx = jnp.clip(t - n_stages + 1, 0, n_mb - 1)
            emit = jnp.logical_and(stage_id == n_stages - 1,
                                   t >= n_stages - 1)
            outs = jnp.where(
                emit,
                jax.lax.dynamic_update_index_in_dim(
                    outs, y, out_idx, 0),
                outs)
            # rotate activations forward one stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # outputs live on the last stage; broadcast to all stages
        outs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    pspec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(pspec, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """The pipeline-bubble overhead the paper cites for the IPU case."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
