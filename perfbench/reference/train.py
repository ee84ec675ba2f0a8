"""Plain reference of the first training steps, in the recipe the
configuration states (Megatron mixed precision): the model's weights are
the bfloat16 rounding of float32 master weights; the forward and backward
passes run in float32 at HIGHEST precision (the ``loss_and_grad`` of the
reference the configuration names, passed in);
global-norm clipping and AdamW with decoupled weight decay under linear
warmup and cosine decay update the float32 masters.

The gradient runs on the device. The optimizer's moments and the masters
stay on the host in numpy (the device holds only the weights, one
gradient and the activations), updated in slices on a few threads.
"""
from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

#: rows of a batch the reference differentiates at once, so that its
#: float32 logits and attention fit one chip beside the weights
BLOCK_ROWS = 2
#: host threads of the optimizer update, and elements per slice
THREADS, SLICE = 8, 1 << 22


def lr_at(opt: dict, step: int) -> float:
    warm = min(step / max(opt["warmup"], 1), 1.0)
    t = min(max((step - opt["warmup"])
                / max(opt["total_steps"] - opt["warmup"], 1), 0.0), 1.0)
    frac = opt.get("min_lr_frac", 0.1)
    return opt["lr"] * warm * (frac + (1 - frac) * 0.5
                               * (1 + math.cos(math.pi * t)))


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))), tree)


def _blocked_loss_and_grad(loss_and_grad, cfg, w, batch, quant):
    """Mean loss and its gradient over all rows, computed in blocks of
    ``BLOCK_ROWS`` rows (every row has the same number of positions, so
    the mean is the mean of the blocks' means)."""
    n = batch["tokens"].shape[0]
    starts = range(0, n, BLOCK_ROWS)
    loss, g = 0.0, None
    for s in starts:
        blk = {k: jnp.asarray(x[s:s + BLOCK_ROWS]) for k, x in batch.items()}
        l_b, g_b = loss_and_grad(cfg, w, blk, quant)
        loss += float(l_b) / len(starts)
        g = g_b if g is None else jax.tree.map(jnp.add, g, g_b)
        del g_b
    if len(starts) > 1:
        g = jax.tree.map(lambda a: a / len(starts), g)
    return loss, g


def _adam_slice(t: int, hp: dict, g, m, v, w) -> None:
    """One AdamW step, in place, on matching flat slices."""
    b1, b2 = np.float32(hp["b1"]), np.float32(hp["b2"])
    m *= b1
    m += (1 - b1) * g
    v *= b2
    g *= g
    v += (1 - b2) * g
    d = (m / np.float32(1 - hp["b1"] ** t)) / (
        np.sqrt(v / np.float32(1 - hp["b2"] ** t)) + np.float32(hp["eps"]))
    if hp["decay"]:
        d += np.float32(hp["weight_decay"]) * w
    w -= np.float32(hp["lr"]) * d


def reference_readings(loss_and_grad, cfg: dict, mix: dict, names: list,
                       w0: list, batches: list, quant=None,
                       rows=None) -> dict:
    """Losses of the steps of ``batches`` from the initial weights ``w0``
    (host arrays, in the order of ``names``), per-leaf norms of the first
    clipped gradient, and per-leaf norms of the masters' change after all
    the steps, the loss and its gradient from ``loss_and_grad(cfg, params,
    batch, quant)``. ``quant="fp8"`` computes every matmul from fp8 operands
    (the control); ``rows`` keeps only those rows of each batch (a fault
    planted in the reference: part of the batch left out)."""
    opt = mix["optimizer"]
    hp = {"b1": opt.get("b1", 0.9), "b2": opt.get("b2", 0.95),
          "eps": opt.get("eps", 1e-8),
          "weight_decay": opt.get("weight_decay", 0.1)}
    clip = opt.get("grad_clip", 1.0)
    master = [np.asarray(a, np.float32).copy() for a in w0]
    m = [np.zeros_like(a) for a in master]
    v = [np.zeros_like(a) for a in master]
    out = {"loss": []}
    pool = ThreadPoolExecutor(THREADS)
    try:
        for s, batch in enumerate(batches):
            if rows is not None:
                batch = {k: x[rows] for k, x in batch.items()}
            # the model's weights: the masters rounded to bf16, held in f32
            # so that the gradient is float32
            w = _f32(_named(names, [jnp.asarray(a.astype(jnp.bfloat16))
                                    for a in master]))
            loss, g = _blocked_loss_and_grad(loss_and_grad, cfg, w, batch,
                                             quant)
            del w
            out["loss"].append(loss)
            norms = [float(x) for x in jax.tree_util.tree_leaves(
                _leaf_norms(g))]
            scale = min(1.0, clip / max(math.sqrt(sum(x * x for x in norms)),
                                        1e-12))
            if s == 0:
                out["grad"] = {n: x * scale for n, x in zip(names, norms)}
            g = [np.asarray(a, np.float32) * np.float32(scale)
                 for a in jax.device_get(jax.tree_util.tree_leaves(g))]
            hp["lr"] = lr_at(opt, s + 1)
            jobs = []
            for gi, mi, vi, wi in zip(g, m, v, master):
                leaf = dict(hp, decay=wi.ndim >= 1)
                flat = [a.reshape(-1) for a in (gi, mi, vi, wi)]
                for lo in range(0, flat[0].size, SLICE):
                    jobs.append(pool.submit(_adam_slice, s + 1, leaf,
                                            *(a[lo:lo + SLICE] for a in flat)))
            for j in jobs:
                j.result()
            del g
    finally:
        pool.shutdown()
    out["change"] = {}
    for n, a, b in zip(names, master, w0):
        d = (a - np.asarray(b, np.float32)).reshape(-1)
        out["change"][n] = float(np.sqrt(np.dot(d, d)))
    return out


@jax.jit
def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _named(names: list, leaves: list) -> dict:
    """The weights' nested layout from leaves in the order of ``names``."""
    nested: dict = {}
    for n, leaf in zip(names, leaves):
        d = nested
        parts = n.split("/")
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = leaf
    return nested

