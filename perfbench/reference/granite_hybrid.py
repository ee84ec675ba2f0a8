"""Plain float32 reference of IBM Granite 4.0-H (``granitemoehybrid``):
Mamba-2 and NoPE attention layers, each followed by a mixture of experts
with a shared expert, and the benchmark's weights for it.

The interface is the one ``reference/model.py`` sets out
(``make_weights``, ``sequence_logits``); a configuration names this
module with ``"reference": "granite_hybrid"``. Nothing here imports the
program: the weights are made here, in the program's layout (a nested
dict of per-period stacked leaves, ``layers/slot<i>``), and handed to
both. Every number the forward pass needs is read from the configuration
file: the layer kinds (``layer_types``), the widths, the four multipliers
and the experts held.

Following the published model (Hugging Face ``granitemoehybrid``):

- embeddings times ``embedding_multiplier``; every residual branch times
  ``residual_multiplier``; logits over the tied table divided by
  ``logits_scaling``; RMSNorm with ``rms_norm_eps`` before each mixer,
  each expert layer and the head;
- Mamba-2 as the per-position recurrence (not the chunked SSD the
  program runs): in_proj to z, x, B, C, dt (no bias); a depthwise causal
  conv of ``mamba_d_conv`` explicit taps with bias over x, B, C, then
  SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``,
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``;
  one group (B and C shared by all heads); the gated RMSNorm
  ``norm(y * silu(z))``; out_proj;
- attention: causal grouped-query attention with no position encoding,
  scores times ``attention_multiplier`` (not ``1/sqrt(head_dim)``), no
  biases;
- experts: the router scores all ``num_experts`` (``num_local_experts``
  held here, the rest on other chips) and keeps ``num_experts_per_tok``,
  softmaxed over their logits; each held expert is a SwiGLU of width
  ``intermediate_size``, computed for every token and weighted by its
  gate where the token chose it (zero elsewhere); the shared SwiGLU
  expert of width ``shared_intermediate_size`` is added for every token.

Departures from the published model: the experts that other chips hold
are left out, as in the program (the result is this chip's share of the
layer); the weights are random (``make_weights``), with the published
Mamba-2 initialisation but for ``A``: ``dt`` log-uniform in [1e-3, 1e-1]
through ``dt_bias`` (the inverse softplus), ``D = 1``, and ``A`` uniform
in ``A_RANGE`` (not [1, 16]) as ``A_log``, so that the served tokens
depend on state carried from chunks before; the tied table's rows are
drawn small (``TOK_NORM``), so that the random model does not just copy
its input.

Every matmul takes a ``quant`` rule for its operands (``None``: float32 at
HIGHEST; ``"fp8"``: the control), as in ``reference/model.py``.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.model import _paths, ein, seed_key

# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

#: the tied table's rows are drawn with this expected norm. Drawn at the
#: usual std 0.02 (a norm of 1.28 at d 4096), the embedding (times 12)
#: makes every position's own token its best logit by ~2 (the other
#: logits spread over ~0.1): the random model only copies its input, so
#: no served token could tell a sound program from a broken one (every
#: fp8 control read 0 at d 1024 and 4096 on the CPU). At norm 1/16 the
#: self term (12 / 16 * norm^2 = 0.003) sits below the other logits'
#: spread (~0.03), and the best token depends on the context.
TOK_NORM = 1.0 / 16

#: ``A = exp(A_log)`` is drawn uniform in this range. At the published
#: [1, 16] every head forgets within tens of tokens (its decay a token is
#: exp(-dt A)), so a state lost at a chunk boundary is forgotten before the
#: prompt's last chunk (128 or 256 tokens) ends, and the served tokens read
#: as a sound run's do. At [0.1, 1] the heads keep their state over 10 to
#: 10,000 tokens, as a trained model's long-memory heads do, and the state
#: a chunk carries moves the served tokens: zeroing it at each boundary
#: flips the best token at 85 % of the served positions after a 640-token
#: prompt at the configuration's widths (gap up to 8.3e-3), against 12 %
#: (6.3e-4) at [1, 16].
A_RANGE = (0.1, 1.0)


def _leaf_init(path: str, shape, key, dtype, taps: int):
    """One leaf of the benchmark's weights, by the leaf's name (``taps``:
    the Mamba conv's width, its fan-in)."""
    parts = path.split("/")
    name, parent = parts[-1], (parts[-2] if len(parts) > 1 else "")
    z = jax.random.normal(key, shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if name in ("scale", "norm_scale"):
        v = 1.0 + 0.02 * z
    elif name == "tok":                            # (V, D): see TOK_NORM
        v = TOK_NORM / np.sqrt(shape[1]) * z
    elif name == "dt_bias":                        # dt log-uniform, inverted
        dt = jnp.exp(u * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
        v = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "A_log":                          # see A_RANGE
        v = jnp.log(A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * u)
    elif name == "D":
        v = jnp.ones(shape, jnp.float32)
    elif name in ("conv_w", "conv_b"):             # Conv1d: fan-in = taps
        v = (2.0 * u - 1.0) / np.sqrt(taps)
    elif parent == "attn" and name in ("wq", "wk", "wv"):
        v = z / np.sqrt(shape[1])                  # (L, D, heads, Dh)
    elif parent == "attn" and name == "wo":
        v = z / np.sqrt(shape[1] * shape[2])       # (L, H, Dh, D)
    elif parent == "experts":                      # (L, E, in, out)
        v = z / np.sqrt(shape[2])
    else:                                          # (L, in, out)
        v = z / np.sqrt(shape[1])
    return v.astype(dtype)


def make_weights(abstract, seed: int, dtype=None, sharding=None):
    """The weights for ``abstract`` (a tree of ShapeDtypeStruct in the
    program's layout), made on the device in one jitted call from
    ``seed``. ``dtype`` overrides each leaf's own dtype."""
    names, leaves, treedef = _paths(abstract)
    taps = next((leaf.shape[1] for n, leaf in zip(names, leaves)
                 if n.endswith("/conv_w")), 1)

    def build(key):
        out = []
        for n, leaf in zip(names, leaves):
            k = jax.random.fold_in(key, zlib.crc32(n.encode()))
            out.append(_leaf_init(n, leaf.shape, k, dtype or leaf.dtype,
                                  taps))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=sharding)(seed_key(seed))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, scale, eps):
    x = _f32(x)
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mamba(cfg, p, h, quant):
    """One Mamba-2 mixer over h (B, S, D), position by position."""
    nh, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, kw = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    di = nh * hp
    b, s, _ = h.shape
    zxbcdt = ein("bsd,de->bse", h, p["in_proj"], quant)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n],
                  zxbcdt[..., 2 * di + 2 * n:])
    w, cb = _f32(p["conv_w"]), _f32(p["conv_b"])   # (K, C), (C,)
    past = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
    conv = cb + sum(w[i] * past[:, i:i + s] for i in range(kw))
    xbc = _silu(conv)
    x, bm, cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))             # (B, S, nh)
    a = -jnp.exp(_f32(p["A_log"]))                            # (nh,)
    x = x.reshape(b, s, nh, hp)

    def step(state, inp):
        xt, bt, ct, dtt = inp          # (B,nh,hp) (B,n) (B,n) (B,nh)
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        yt = jnp.einsum("bhpn,bn->bhp", state, ct, precision="highest")
        return state, yt

    moved = [jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, dt)]
    _, y = jax.lax.scan(step, jnp.zeros((b, nh, hp, n), jnp.float32), moved)
    y = jnp.moveaxis(y, 0, 1) + _f32(p["D"])[:, None] * x     # (B,S,nh,hp)
    g = _rms(y.reshape(b, s, di) * _silu(z), p["norm_scale"],
             cfg["rms_norm_eps"])
    return ein("bse,ed->bsd", g, p["out_proj"], quant)


def _attention(cfg, p, h, quant, q_block=512):
    """Causal GQA over h (B, S, D) with no position encoding, a block of
    queries at a time (so that 4k positions fit)."""
    q = ein("bsd,dhk->bshk", h, p["wq"], quant)
    k = ein("bsd,dhk->bshk", h, p["wk"], quant)
    v = ein("bsd,dhk->bshk", h, p["wv"], quant)
    b, s, nh, dh = q.shape
    kh = k.shape[2]
    q = q.reshape(b, s, kh, nh // kh, dh) * cfg["attention_multiplier"]
    qb = min(q_block, s)
    assert s % qb == 0, (s, qb)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        sc = ein("bqkgd,btkd->bkgqt", qi, k, quant)
        mask = jnp.arange(s)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return ein("bkgqt,btkd->bqkgd", w, v, quant)

    out = jax.lax.map(block, jnp.arange(s // qb))   # (nq, b, qb, kh, g, dh)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, nh, dh)
    return ein("bshk,hkd->bsd", out, p["wo"], quant)


def _swiglu(p, h, quant, pre=""):
    a = ein("bsd,df->bsf", h, p[pre + "wi_gate"], quant)
    u = ein("bsd,df->bsf", h, p[pre + "wi_up"], quant)
    return ein("bsf,fd->bsd", _silu(a) * u, p[pre + "wo"], quant)


def _experts(cfg, p, h, quant):
    """The held experts' share of the expert layer, and the shared one."""
    logits = ein("bsd,de->bse", h, p["router"], quant)        # all experts
    top, idx = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    gates = jax.nn.softmax(top, axis=-1)
    held = cfg["num_local_experts"]

    def one(e):
        pe = jax.tree.map(lambda t: t[e], p["experts"])
        gate = jnp.where(idx == e, gates, 0.0).sum(-1)        # (B, S)
        return _swiglu(pe, h, quant) * gate[..., None]

    y = jax.lax.map(one, jnp.arange(held)).sum(0)
    return y + _swiglu(p["shared"], h, quant)


def hidden(cfg, params, tokens, *, quant=None):
    """Final normed hidden states (B, S, D) in float32."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    layers = params["layers"]
    period = len(layers)
    x = _f32(jnp.take(params["embed"]["tok"], tokens, axis=0)) \
        * cfg["embedding_multiplier"]
    for i, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        lp = jax.tree.map(lambda t: t[i // period], layers[f"slot{i % period}"])
        h = _rms(x, lp["norm1"]["scale"], eps)
        if kind == "mamba":
            x = x + res * _mamba(cfg, lp["mamba"], h, quant)
        else:
            x = x + res * _attention(cfg, lp["attn"], h, quant)
        h = _rms(x, lp["norm2"]["scale"], eps)
        x = x + res * _experts(cfg, lp["moe"], h, quant)
    return _rms(x, params["final_norm"]["scale"], eps)


def logits_of(cfg, params, h, quant=None):
    table = params["embed"]["tok"][:cfg["vocab_size"]]
    return ein("bsd,vd->bsv", h, table, quant) / cfg["logits_scaling"]


_KEYS = ("num_hidden_layers", "layer_types", "mamba_n_heads", "mamba_d_head",
         "mamba_d_state", "mamba_d_conv", "num_local_experts",
         "num_experts_per_tok", "attention_multiplier", "embedding_multiplier",
         "residual_multiplier", "logits_scaling", "rms_norm_eps",
         "vocab_size")


def _items(cfg):
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in _KEYS)


@partial(jax.jit, static_argnums=(0, 2))
def _seq_logits(cfg_items, params, quant, tokens):
    cfg = dict(cfg_items)
    return logits_of(cfg, params, hidden(cfg, params, tokens, quant=quant),
                     quant)


def sequence_logits(cfg, params, tokens, quant=None):
    """Logits (1, S, vocab) of one token sequence (1, S). Weights may be
    bf16: each matmul upcasts its own operands."""
    return _seq_logits(_items(cfg), params, quant, tokens)
