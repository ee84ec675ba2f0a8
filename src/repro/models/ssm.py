"""Mamba2 — SSD (state-space duality) blocks.

Training/prefill uses the chunked SSD algorithm (quadratic within a chunk,
linear across chunks via a ``lax.scan`` state recurrence). Decode is the
O(1)-per-token state recurrence. ngroups=1 (B/C shared across heads), as in
the published mamba2-1.3b config.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, dtype_of, opt_barrier, param_dtype_of

Params = Any


def mamba_init(key, c: ModelConfig) -> Params:
    pd = param_dtype_of(c)
    di, ns, nh, kw = c.d_inner, c.ssm_state, c.ssm_nheads, c.ssm_conv
    conv_ch = di + 2 * ns
    ks = jax.random.split(key, 4)
    return {
        "in_proj": dense_init(ks[0], c.d_model, 2 * di + 2 * ns + nh, pd),
        "conv_w": (jax.random.normal(ks[1], (kw, conv_ch), jnp.float32)
                   * (1.0 / kw)).astype(pd),
        "conv_b": jnp.zeros((conv_ch,), pd),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "norm_scale": jnp.ones((di,), pd),
        "out_proj": dense_init(ks[2], di, c.d_model, pd),
    }


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv. xbc: (B, S, C); w: (K, C); ``state``
    (B, K-1, C): the inputs before the first position (zeros if None)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        pad = jnp.concatenate([state.astype(xbc.dtype), xbc], axis=1)
    # unrolled taps: K is tiny (4); avoids conv layout headaches under SPMD
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i] for i in range(k))
    return jax.nn.silu((out + b).astype(jnp.float32)).astype(xbc.dtype)


def _conv_step(state: jax.Array, x_new: jax.Array, w: jax.Array, b: jax.Array):
    """Single-token conv. state: (B, K-1, C); x_new: (B, 1, C)."""
    window = jnp.concatenate([state, x_new], axis=1)  # (B, K, C)
    out = jnp.einsum("bkc,kc->bc", window, w) + b
    out = jax.nn.silu(out.astype(jnp.float32)).astype(x_new.dtype)
    return out[:, None, :], window[:, 1:, :]


def _split_proj(c: ModelConfig, zxbcdt: jax.Array):
    di, ns, nh = c.d_inner, c.ssm_state, c.ssm_nheads
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * ns]
    dt = zxbcdt[..., di + di + 2 * ns:]
    return z, xbc, dt


def _gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
                eps: float = 1e-5) -> jax.Array:
    y, z = opt_barrier((y, z))  # see common.apply_norm
    g = (y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)).astype(jnp.float32)
    ms = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    return (g * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(y.dtype)


# ---------------------------------------------------------------------------
# SSD core (chunked scan)
# ---------------------------------------------------------------------------


def _segsum(a: jax.Array) -> jax.Array:
    """a: (..., L). Returns (..., L, L) with sum_{j<i..i} decays, -inf above diag."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    ss = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, ss, -jnp.inf)


def ssd_chunked(xdt: jax.Array, dA: jax.Array, B: jax.Array, C: jax.Array,
                chunk: int, h0: jax.Array | None = None,
                unroll: bool = False):
    """Chunked SSD as a scan over chunks (memory-bounded).

    xdt: (b, s, h, p) — inputs pre-multiplied by dt
    dA:  (b, s, h)    — dt * A (negative)
    B,C: (b, s, n)    — shared across heads (ngroups=1)
    Returns y: (b, s, h, p) and final state (b, h, p, n).

    Each scan step materializes only ONE chunk's (l, l) decay matrix; the
    body is remat'd so the backward recomputes it instead of saving fp32
    decay blocks stacked across chunks (same strategy as the q-chunked
    attention — see EXPERIMENTS.md par.Perf).
    """
    b, s, nh, p = xdt.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    xdt_c = xdt.reshape(b, nc, chunk, nh, p).transpose(1, 0, 2, 3, 4)
    dA_c = dA.reshape(b, nc, chunk, nh).transpose(1, 0, 2, 3)
    B_c = B.reshape(b, nc, chunk, n).transpose(1, 0, 2, 3)
    C_c = C.reshape(b, nc, chunk, n).transpose(1, 0, 2, 3)
    if h0 is None:
        h0 = jnp.zeros((b, nh, p, n), jnp.float32)

    def body(h, inp):
        xc, dac, bc, cc = inp          # (b,l,h,p) (b,l,h) (b,l,n) (b,l,n)
        dac = dac.astype(jnp.float32)
        da_cs = jnp.cumsum(dac, axis=1)                    # (b,l,h)
        # intra-chunk (one (l,l) decay block, transient)
        L = jnp.exp(_segsum(dac.transpose(0, 2, 1)))       # (b,h,l,l)
        scores = jnp.einsum("bln,bsn->bls", cc, bc)        # (b,l,s)
        y = jnp.einsum("bls,bhls,bshp->blhp", scores, L,
                       xc.astype(jnp.float32), optimize="optimal")
        # carried-in state contribution
        y = y + jnp.einsum("bln,bhpn,blh->blhp", cc.astype(jnp.float32),
                           h, jnp.exp(da_cs), optimize="optimal")
        # state update
        end = da_cs[:, -1]                                  # (b,h)
        decay_to_end = jnp.exp(end[:, None] - da_cs)        # (b,l,h)
        h_new = (h * jnp.exp(end)[..., None, None]
                 + jnp.einsum("bln,blh,blhp->bhpn", bc.astype(jnp.float32),
                              decay_to_end, xc.astype(jnp.float32),
                              optimize="optimal"))
        return h_new, y.astype(xdt.dtype)

    body = jax.checkpoint(body, policy=None)
    # metrics pass: cap the unroll at 16 chunk bodies — the SSD core is
    # <10% of a mamba block's FLOPs (the projections outside this scan
    # dominate), so the residual undercount on long sequences is bounded
    # and documented in EXPERIMENTS.md par.Dry-run; full unroll of 128
    # chunks x 14 layers made XLA:CPU compiles take tens of minutes.
    u = min(16, nc) if unroll else 1
    h_fin, ys = jax.lax.scan(body, h0.astype(jnp.float32),
                             (xdt_c, dA_c, B_c, C_c),
                             unroll=(True if (unroll and nc <= 16) else u))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, s, nh, p)
    return y, h_fin  # state stays fp32 (prefill->decode continuity)


def ssd_decode_step(h: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
                    B: jax.Array, C: jax.Array):
    """One-token SSD recurrence.

    h: (b, nh, p, n); x: (b, nh, p); dt: (b, nh); A: (nh,); B/C: (b, n).
    """
    dA = jnp.exp(dt * A)  # (b, nh)
    dBx = jnp.einsum("bh,bhp,bn->bhpn", dt, x, B)
    h_new = h * dA[..., None, None] + dBx
    y = jnp.einsum("bhpn,bn->bhp", h_new, C)
    return y, h_new


# ---------------------------------------------------------------------------
# Full Mamba2 block
# ---------------------------------------------------------------------------


def mamba_forward(c: ModelConfig, p: Params, x: jax.Array,
                  h0: jax.Array | None = None, return_state: bool = False,
                  unroll: bool = False, conv0: jax.Array | None = None,
                  lengths: jax.Array | None = None):
    """x: (B, S, D) -> (B, S, D). Chunked SSD over the sequence.

    ``h0`` (B, nh, hp, n) and ``conv0`` (B, K-1, C) are the SSM and conv
    state carried in from the positions before this call (zeros if None),
    so a prompt can be fed in pieces. ``lengths`` (B,) int32 marks each
    row's valid positions ``[0, length)``: later positions get ``dt = 0``
    (the SSD state passes through them unchanged) and the returned conv
    tail is the last K-1 valid inputs of [carried state ++ this call].
    """
    b, s, _ = x.shape
    di, ns, nh, hp = c.d_inner, c.ssm_state, c.ssm_nheads, c.ssm_headdim
    km1 = c.ssm_conv - 1
    z, xbc_raw, dt_raw = _split_proj(c, x @ p["in_proj"])
    if conv0 is None and lengths is None:
        conv_tail = xbc_raw[:, -km1:, :]  # for decode continuity
    else:
        hist = jnp.concatenate(
            [jnp.zeros((b, km1, xbc_raw.shape[-1]), xbc_raw.dtype)
             if conv0 is None else conv0.astype(xbc_raw.dtype), xbc_raw],
            axis=1)                                    # (b, K-1+s, C)
        end = jnp.full((b,), s, jnp.int32) if lengths is None else lengths
        at = end[:, None] + jnp.arange(km1)[None, :]   # last K-1 valid
        conv_tail = jnp.take_along_axis(hist, at[..., None], axis=1)
    xbc = _causal_conv(xbc_raw, p["conv_w"], p["conv_b"], conv0)
    xin, B, C = xbc[..., :di], xbc[..., di:di + ns], xbc[..., di + ns:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (b,s,nh)
    if lengths is not None:
        dt = jnp.where((jnp.arange(s)[None, :] < lengths[:, None])[..., None],
                       dt, 0.0)
    A = -jnp.exp(p["A_log"])  # (nh,)
    xh = xin.reshape(b, s, nh, hp)
    xdt = xh * dt[..., None].astype(xh.dtype)
    dA = dt * A
    chunk = min(c.ssm_chunk, s)
    while s % chunk:  # largest divisor of s not above ssm_chunk
        chunk -= 1
    y, h_fin = ssd_chunked(xdt, dA, B, C, chunk, h0=h0, unroll=unroll)
    y = y.astype(xh.dtype) + xh * p["D"].astype(xh.dtype)[:, None]
    y = y.reshape(b, s, di)
    y = _gated_norm(y, z, p["norm_scale"])
    out = (y @ p["out_proj"]).astype(x.dtype)
    if return_state:
        return out, (conv_tail, h_fin)
    return out


def mamba_decode(c: ModelConfig, p: Params, x: jax.Array,
                 conv_state: jax.Array, ssm_state: jax.Array):
    """One-token decode. x: (B, 1, D). Returns (out, conv_state, ssm_state)."""
    b = x.shape[0]
    di, ns, nh, hp = c.d_inner, c.ssm_state, c.ssm_nheads, c.ssm_headdim
    z, xbc, dt_raw = _split_proj(c, x @ p["in_proj"])
    xbc, conv_state = _conv_step(conv_state, xbc, p["conv_w"], p["conv_b"])
    xin, B, C = xbc[..., :di], xbc[..., di:di + ns], xbc[..., di + ns:]
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])  # (b,nh)
    A = -jnp.exp(p["A_log"])
    xh = xin[:, 0].reshape(b, nh, hp)
    in_state_dtype = ssm_state.dtype
    y, ssm_state = ssd_decode_step(
        ssm_state, xh, dt.astype(jnp.float32), A, B[:, 0], C[:, 0])
    ssm_state = ssm_state.astype(in_state_dtype)
    y = y.astype(xh.dtype) + xh * p["D"].astype(xh.dtype)[:, None]
    y = y[:, None].reshape(b, 1, di)
    y = _gated_norm(y, z, p["norm_scale"])
    return (y @ p["out_proj"]).astype(x.dtype), conv_state, ssm_state


def mamba_state_shapes(c: ModelConfig, batch: int, dtype):
    conv = (batch, c.ssm_conv - 1, c.d_inner + 2 * c.ssm_state)
    ssm = (batch, c.ssm_nheads, c.ssm_headdim, c.ssm_state)
    return conv, ssm
