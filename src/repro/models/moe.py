"""Mixture-of-Experts FFN: top-k router + capacity-based scatter dispatch.

Dispatch is gather/scatter based (argsort-free GShard-style positions via
cumsum ranking), NOT the one-hot einsum formulation — the einsum dispatch is
O(T^2) FLOPs per group and would dominate the roofline. With the expert dim
sharded over the mesh ``model`` axis, GSPMD lowers the scatter/gather pair
to all-to-all collectives (expert parallelism).

``moe_share_forward`` is the dropless layer (``moe_dispatch ==
"dropless"``): it holds experts ``[0, experts_held)`` of the ``n_experts``
the router scores (one chip's share of an expert-parallel deployment), and
computes only their part of the routed result, by a grouped matmul over
the held experts, dropping no token. ``moe_layer`` dispatches between the
two.

``moe_forward_dense`` is the pure/naive oracle used by tests.
"""
from __future__ import annotations

import contextlib
import math
from contextvars import ContextVar
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import apply_mlp, dense_init, mlp_init, param_dtype_of

Params = Any

# Dispatch implementation: "scatter" (capacity-based, lowers to all-to-all
# when experts are sharded over the tp axis) or "dense" (masked batched
# einsum over ALL experts — compute overhead E/top_k, but no scatter; the
# right choice when E doesn't divide the tp axis, where GSPMD would
# replicate the (E*C, D) dispatch buffer on every device).
_MOE_IMPL: ContextVar[str] = ContextVar("moe_impl", default="scatter")

# Optional sharding constraint for the dispatch buffer's feature dim.
# Without it GSPMD materializes the (E*C, d) buffer replicated and
# all-reduces it per MoE layer (measured 1.8 TB/step wire on llama4
# prefill); with d sharded over tp, the expert-sharded weights pull the
# buffer through an all-to-all instead (the intended EP dataflow).
_MOE_BUF_SPEC: ContextVar = ContextVar("moe_buf_spec", default=None)


@contextlib.contextmanager
def moe_impl(name: str, buf_spec=None):
    tok = _MOE_IMPL.set(name)
    tok2 = _MOE_BUF_SPEC.set(buf_spec)
    try:
        yield
    finally:
        _MOE_IMPL.reset(tok)
        _MOE_BUF_SPEC.reset(tok2)


def _buf_hint(x):
    spec = _MOE_BUF_SPEC.get()
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def moe_init(key, c: ModelConfig) -> Params:
    """Router over all ``n_experts``; weights of the held experts only."""
    pd = param_dtype_of(c)
    eff = c.expert_d_ff or c.d_ff
    ks = jax.random.split(key, c.n_experts + 2)
    experts = [mlp_init(ks[i], c, eff) for i in range(c.experts_held)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *experts)
    p = {
        "router": dense_init(ks[-1], c.d_model, c.n_experts, jnp.float32),
        "experts": stacked,
    }
    if c.shared_d_ff:
        p["shared"] = mlp_init(ks[-2], c, c.shared_d_ff)
    return p


def router_topk(c: ModelConfig, p: Params, x2d: jax.Array):
    """x2d: (T, D) -> (weights (T,k), experts (T,k), aux_loss scalar)."""
    logits = (x2d.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    weights, experts = jax.lax.top_k(probs, c.top_k)            # (T, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss
    T = x2d.shape[0]
    me = probs.mean(axis=0)                                     # (E,)
    one_hot = jax.nn.one_hot(experts[:, 0], c.n_experts, dtype=jnp.float32)
    ce = one_hot.mean(axis=0)
    aux = c.n_experts * jnp.sum(me * ce)
    return weights, experts, aux


def expert_capacity(c: ModelConfig, n_tokens: int) -> int:
    cap = int(math.ceil(n_tokens * c.top_k * c.capacity_factor / c.n_experts))
    return max(cap, 4)


def _apply_experts(c: ModelConfig, experts: Params, buf: jax.Array) -> jax.Array:
    """buf: (E, C, D) -> (E, C, D) via per-expert MLP (batched einsum)."""
    if c.act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", buf, experts["wi_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf, experts["wi_up"])
        h = jax.nn.silu(g) * u
    else:
        h = jnp.einsum("ecd,edf->ecf", buf, experts["wi"])
        if "bi" in experts:
            h = h + experts["bi"][:, None]
        h = jax.nn.gelu(h)
    out = jnp.einsum("ecf,efd->ecd", h, experts["wo"])
    if "bo" in experts:
        out = out + experts["bo"][:, None]
    return out


def moe_forward(c: ModelConfig, p: Params, x: jax.Array):
    """x: (B, S, D) -> (y (B,S,D), aux_loss)."""
    if _MOE_IMPL.get() == "dense":
        return moe_forward_einsum(c, p, x)
    b, s, d = x.shape
    T = b * s
    x2d = x.reshape(T, d)
    weights, experts_idx, aux = router_topk(c, p, x2d)
    C = expert_capacity(c, T)
    E = c.n_experts

    # position of each (token, choice) within its expert, via cumsum ranking
    sel = jax.nn.one_hot(experts_idx, E, dtype=jnp.int32)       # (T, k, E)
    sel_flat = sel.reshape(T * c.top_k, E)
    pos = jnp.cumsum(sel_flat, axis=0) * sel_flat - 1           # (T*k, E)
    pos_in_expert = pos.max(axis=-1)                            # (T*k,)
    keep = (pos_in_expert >= 0) & (pos_in_expert < C)
    dest = experts_idx.reshape(-1) * C + jnp.clip(pos_in_expert, 0, C - 1)
    dest = jnp.where(keep, dest, E * C)                         # overflow slot

    xk = jnp.repeat(x2d, c.top_k, axis=0)                       # (T*k, D)
    buf = _buf_hint(jnp.zeros((E * C + 1, d), x.dtype).at[dest].add(xk))
    buf = buf[:-1].reshape(E, C, d)

    out_buf = _apply_experts(c, p["experts"], buf).reshape(E * C, d)
    out_buf = _buf_hint(
        jnp.concatenate([out_buf, jnp.zeros((1, d), x.dtype)]))

    gathered = out_buf[dest]                                    # (T*k, D)
    wk = (weights.reshape(-1, 1) * keep[:, None]).astype(x.dtype)
    y = (gathered * wk).reshape(T, c.top_k, d).sum(axis=1)

    if c.shared_d_ff:
        y = y + apply_mlp(c, p["shared"], x2d)
    return y.reshape(b, s, d), aux


def _grouped_mlp(c: ModelConfig, experts: Params, xs: jax.Array,
                 sizes: jax.Array) -> jax.Array:
    """Per-expert MLP of the rows ``xs`` (R, D), sorted by expert with
    ``sizes[e]`` rows of expert ``e``; rows past ``sizes.sum()`` give 0."""
    def mm(a, w):
        return jax.lax.ragged_dot(a, w.astype(a.dtype), sizes)
    if c.act == "swiglu":
        h = jax.nn.silu(mm(xs, experts["wi_gate"])) * mm(xs, experts["wi_up"])
    else:
        h = jax.nn.gelu(mm(xs, experts["wi"]))
    return mm(h, experts["wo"])


def route_rows(c: ModelConfig) -> int:
    """Rows of the grouped-matmul buffer per token: a token's ``top_k``
    experts are distinct, so at most ``min(top_k, experts_held)`` of its
    choices land here."""
    return min(c.top_k, c.experts_held)


def moe_share_forward(c: ModelConfig, p: Params, x: jax.Array,
                      valid: jax.Array | None = None):
    """The held experts' share of the layer, dropping no token.

    x: (B, S, D); ``valid`` (B, S) bool marks real tokens (the rest are
    routed nowhere). The router scores all ``n_experts`` and keeps its
    ``top_k``, softmaxed over their logits; the choices that land on a
    held expert (ids below ``experts_held``) are sorted by expert into a
    static buffer of ``B*S*route_rows`` rows and go through one grouped
    matmul a weight. The shared expert is added in full. Returns
    (y (B, S, D), routed (int32: real-token choices on held experts),
    aux_loss).
    """
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    weights, idx, aux = router_topk(c, p, x2d)
    k, held = c.top_k, c.experts_held
    mine = idx < held
    if valid is not None:
        mine = mine & valid.reshape(t, 1)
    key = jnp.where(mine, idx, held).reshape(-1)              # (T*k,)
    order = jnp.argsort(key, stable=True)[:t * route_rows(c)]
    sizes = jnp.zeros((held,), jnp.int32).at[key].add(1, mode="drop")
    rows = order // k
    out = _grouped_mlp(c, p["experts"], x2d[rows], sizes)
    w = weights.reshape(-1)[order][:, None]
    out = jnp.where((key[order] < held)[:, None],
                    out.astype(jnp.float32) * w, 0.0)
    y = jnp.zeros((t, d), jnp.float32).at[rows].add(out).astype(x.dtype)
    if c.shared_d_ff:
        y = y + apply_mlp(c, p["shared"], x2d)
    return y.reshape(b, s, d), jnp.sum(mine, dtype=jnp.int32), aux


def moe_layer(c: ModelConfig, p: Params, x: jax.Array,
              valid: jax.Array | None = None):
    """The expert layer as ``c.moe_dispatch`` says: (y, routed, aux_loss),
    ``routed`` None on the capacity path, which counts nothing."""
    if c.moe_dispatch == "dropless":
        return moe_share_forward(c, p, x, valid)
    y, aux = moe_forward(c, p, x)
    return y, None, aux


def moe_forward_einsum(c: ModelConfig, p: Params, x: jax.Array):
    """Masked batched-einsum MoE (all experts on all tokens; no dropping).

    Shards cleanly with the expert FFN dim over tp: (T, E, F) activations
    stay local, the combine einsum contracts (E, F) -> one small AR. Used
    for archs whose expert count doesn't divide the tp axis (DESIGN.md
    par.5: granite-moe's 40 experts vs the 16-way model axis).
    """
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    weights, experts_idx, aux = router_topk(c, p, x2d)
    # dense (T, E) weight matrix with zeros for unrouted experts
    wfull = jnp.zeros((b * s, c.n_experts), jnp.float32)
    wfull = wfull.at[jnp.arange(b * s)[:, None], experts_idx].set(weights)
    ex = p["experts"]
    if c.act == "swiglu":
        g = jnp.einsum("td,edf->tef", x2d, ex["wi_gate"])
        u = jnp.einsum("td,edf->tef", x2d, ex["wi_up"])
        h = jax.nn.silu(g) * u
    else:
        h = jnp.einsum("td,edf->tef", x2d, ex["wi"])
        if "bi" in ex:
            h = h + ex["bi"][None]
        h = jax.nn.gelu(h)
    y = jnp.einsum("tef,te,efd->td", h, wfull.astype(h.dtype), ex["wo"])
    if "bo" in ex:
        y = y + jnp.einsum("te,ed->td", wfull.astype(h.dtype), ex["bo"])
    if c.shared_d_ff:
        y = y + apply_mlp(c, p["shared"], x2d)
    return y.reshape(b, s, d), aux


def moe_forward_dense(c: ModelConfig, p: Params, x: jax.Array):
    """Oracle: loop over experts with dense masks (no capacity drops)."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    weights, experts_idx, aux = router_topk(c, p, x2d)
    y = jnp.zeros_like(x2d)
    for e in range(c.experts_held):
        pe = jax.tree.map(lambda w: w[e], p["experts"])
        ye = apply_mlp(c, pe, x2d)
        w_e = jnp.where(experts_idx == e, weights, 0.0).sum(-1)  # (T,)
        y = y + ye * w_e[:, None].astype(x.dtype)
    if c.shared_d_ff:
        y = y + apply_mlp(c, p["shared"], x2d)
    return y.reshape(b, s, d), aux
