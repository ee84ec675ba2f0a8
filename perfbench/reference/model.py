"""Plain float32 reference of the decoder-only transformers the cells run,
and the benchmark's own weights.

A configuration names its reference under ``"reference"`` (this module,
``model``, where it names none), and the drivers find it by that name
(``perfbench.model.reference_of``). A reference is a module of
``perfbench/reference/`` that provides:

- ``make_weights(abstract, seed, dtype=None, sharding=None)``: the
  weights, in the program's layout, for a tree of ShapeDtypeStruct, made
  on the device from ``seed``; every cell's weights come from here;
- ``sequence_logits(cfg, params, tokens, quant=None)``: float32 logits
  (1, S, vocab) of one token sequence (1, S), for serving;
- ``loss_and_grad(cfg, params, batch, quant=None)``: the mean next-token
  loss of ``batch`` (``tokens``, ``labels``) and its gradient, for
  training;

with ``quant`` in ``{None, "fp8"}``: ``None`` is the reference, and
``"fp8"`` is the control. A new reference may import ``ein``, ``_q`` and
``seed_key`` from here rather than copy them.

Nothing here imports the program. The weights are made by the benchmark
from the seed, in the layout the program reads (a nested dict of stacked
per-layer leaves), and handed to both the program and this reference.

The reference follows the published descriptions:

- GPT (CARAML's Megatron GPT): learned-free rotary positions (rotate
  halves), pre-LayerNorm, multi-head attention with q/k/v/o biases, a
  tanh-approximated GELU MLP with biases, tied input/output embeddings.
- Granite 8B Code: pre-RMSNorm, grouped-query attention (32 query heads,
  8 KV heads of 128), rotary theta 1e7, SwiGLU MLP, untied output head.

Every matmul takes a ``quant`` rule for its operands: ``None`` keeps
float32 at HIGHEST precision; ``"fp8"`` rounds both operands to
float8_e4m3fn with a per-tensor scale first (the control of a bf16
configuration: the nearest precision below the one it states).
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q(x, quant):
    """``x`` rounded as ``quant`` says. The rounding passes gradients
    straight through (the cotangents stay float32; only the operands the
    matmuls see are rounded)."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / F8_MAX)
    r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(r - x)


def ein(spec, a, b, quant=None):
    return jnp.einsum(spec, _q(a.astype(jnp.float32), quant),
                      _q(b.astype(jnp.float32), quant), precision=HI)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def _leaf_init(path: str, shape, key, dtype):
    """One leaf of the benchmark's weights, by the leaf's name."""
    name = path.split("/")[-1]
    parent = path.split("/")[-2] if "/" in path else ""
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":
        v = 1.0 + 0.02 * z
    elif name in ("bias", "bq", "bk", "bv", "bi", "bo"):
        v = 0.02 * z
    elif name in ("tok", "head", "pos"):
        v = 0.02 * z
    elif parent == "attn" and name in ("wq", "wk", "wv"):
        v = z / np.sqrt(shape[1])                  # (L, D, heads, Dh)
    elif parent == "attn" and name == "wo":
        v = z / np.sqrt(shape[1] * shape[2])       # (L, H, Dh, D)
    else:                                          # mlp (L, in, out)
        v = z / np.sqrt(shape[1])
    return v.astype(dtype)


def _paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    return names, [leaf for _, leaf in flat], treedef


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also beyond 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(abstract, seed: int, dtype=None, sharding=None):
    """The weights for ``abstract`` (a tree of ShapeDtypeStruct in the
    program's layout), made on the device in one jitted call from
    ``seed``. ``dtype`` overrides each leaf's own dtype."""
    names, leaves, treedef = _paths(abstract)

    def build(key):
        out = []
        for n, leaf in zip(names, leaves):
            k = jax.random.fold_in(key, zlib.crc32(n.encode()))
            out.append(_leaf_init(n, leaf.shape, k, dtype or leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=sharding)(seed_key(seed))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _norm(cfg, p, x, eps=1e-5):
    x = x.astype(jnp.float32)
    if cfg["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32))
    ms = jnp.square(x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * p["scale"].astype(jnp.float32)


def _rope(x, pos, theta):
    """x (B, S, heads, Dh); rotate halves: [x1 c - x2 s, x2 c + x1 s]."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = pos[..., None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(cfg, p, h, pos, quant, q_block):
    ab = lambda name: p[name].astype(jnp.float32) if name in p else 0.0  # noqa: E731
    q = ein("bsd,dhk->bshk", h, p["wq"], quant) + ab("bq")
    k = ein("bsd,dhk->bshk", h, p["wk"], quant) + ab("bk")
    v = ein("bsd,dhk->bshk", h, p["wv"], quant) + ab("bv")
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    b, s, nh, dh = q.shape
    g = nh // k.shape[2]
    q = q.reshape(b, s, k.shape[2], g, dh) / np.sqrt(dh)
    qb = min(q_block, s)
    assert s % qb == 0, (s, qb)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        sc = ein("bqkgd,btkd->bkgqt", qi, k, quant)
        qpos = i * qb + jnp.arange(qb)
        mask = jnp.arange(s)[None, :] <= qpos[:, None]
        sc = jnp.where(mask, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return ein("bkgqt,btkd->bqkgd", w, v, quant)

    out = jax.lax.map(block, jnp.arange(s // qb))       # (nq, b, qb, kh, g, dh)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, nh, dh)
    o = ein("bshk,hkd->bsd", out, p["wo"], quant)
    return o + ab("bo") if "bo" in p else o


def _mlp(cfg, p, h, quant):
    bias = lambda n: p[n].astype(jnp.float32) if n in p else 0.0  # noqa: E731
    if cfg["hidden_act"] == "silu":                  # SwiGLU
        a = ein("bsd,df->bsf", h, p["wi_gate"], quant)
        u = ein("bsd,df->bsf", h, p["wi_up"], quant)
        z = jax.nn.silu(a + bias("bi")) * u
    else:                                             # tanh-approx GELU
        z = jax.nn.gelu(ein("bsd,df->bsf", h, p["wi"], quant) + bias("bi"),
                        approximate=True)
    return ein("bsf,fd->bsd", z, p["wo"], quant) + bias("bo")


def hidden(cfg, params, tokens, *, quant=None, q_block=512, remat=True):
    """Final normed hidden states (B, S, D) in float32."""
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)

    def layer(x, lp):
        lp = lp["slot0"]
        x = x + _attention(cfg, lp["attn"], _norm(cfg, lp["norm1"], x), pos,
                           quant, q_block)
        return x + _mlp(cfg, lp["mlp"], _norm(cfg, lp["norm2"], x), quant), None

    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _norm(cfg, params["final_norm"], x)


def logits_of(cfg, params, h, quant=None):
    table = params["embed"]["head" if "head" in params["embed"] else "tok"]
    return ein("bsd,vd->bsv", h, table[:cfg["vocab_size"]], quant)


def loss(cfg, params, tokens, labels, quant=None):
    """Mean next-token cross-entropy over every position."""
    lg = logits_of(cfg, params, hidden(cfg, params, tokens, quant=quant),
                   quant)
    lse = jax.nn.logsumexp(lg, -1)
    picked = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


@partial(jax.jit, static_argnums=(0, 3))
def _loss_grad(cfg_items, params, batch, quant):
    cfg = dict(cfg_items)
    return jax.value_and_grad(
        lambda p: loss(cfg, p, batch["tokens"], batch["labels"], quant))(params)


def loss_and_grad(cfg, params, batch, quant=None):
    return _loss_grad(_items(cfg), params, batch, quant)


def _items(cfg):
    keys = ("norm", "hidden_act", "rope_theta", "vocab_size")
    return tuple((k, cfg[k]) for k in keys)


@partial(jax.jit, static_argnums=(0, 2))
def _seq_logits(cfg_items, params, quant, tokens):
    cfg = dict(cfg_items)
    h = hidden(cfg, params, tokens, quant=quant, remat=False)
    return logits_of(cfg, params, h, quant)


def sequence_logits(cfg, params, tokens, quant=None):
    """Logits (1, S, vocab) of one token sequence (1, S). Weights may be
    bf16: each matmul upcasts its own operands, so no float32 copy of the
    whole model is ever held."""
    return _seq_logits(_items(cfg), params, quant, tokens)
