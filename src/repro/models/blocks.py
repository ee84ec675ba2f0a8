"""Decoder blocks + period-pattern LayerStack (scan over layers).

Heterogeneous layer patterns (Jamba's 1-attn-per-8, llama4's MoE-every-2nd)
are handled by unrolling one *period* of the pattern inside the scan body
and scanning over ``n_layers // period`` stacked parameter pytrees. This
keeps the HLO compact (compile time ~O(period), not O(n_layers)) and gives
natural full-activation-recomputation boundaries (the paper's Megatron
setup enables activation recomputation).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import apply_mlp, apply_norm, mlp_init, norm_init

Params = Any


def period_of(c: ModelConfig) -> int:
    p = 1
    if c.family == "hybrid":
        p = c.attn_layer_period
    if c.n_experts:
        p = max(p, c.moe_layer_step)
        assert p % c.moe_layer_step == 0, "incompatible layer pattern"
    assert c.n_layers % p == 0, (c.n_layers, p)
    return p


def slot_kinds(c: ModelConfig) -> list[tuple[str, Optional[str]]]:
    """Per-slot (mixer, ffn) kinds for one period of the layer pattern."""
    kinds = []
    for i in range(period_of(c)):
        mixer = "attn" if c.is_attn_layer(i) else "mamba"
        if c.family == "ssm":
            ffn = None
        elif c.is_moe_layer(i):
            ffn = "moe"
        elif c.d_ff:
            ffn = "mlp"
        else:
            ffn = None
        kinds.append((mixer, ffn))
    return kinds


def _res(c: ModelConfig, h: jax.Array) -> jax.Array:
    """A residual branch's output times the configuration's multiplier."""
    return h if c.resid_mult == 1.0 else h * jnp.asarray(c.resid_mult,
                                                         h.dtype)


def routes_share(c: ModelConfig) -> bool:
    """Whether the stack's expert layers are the dropless held share
    (``moe.moe_share_forward``), which counts the choices it routes."""
    return c.moe_dispatch == "dropless" \
        and any(ffn == "moe" for _m, ffn in slot_kinds(c))


def _ffn(c: ModelConfig, sp: Params, ffn: Optional[str], x: jax.Array,
         valid=None):
    """The slot's FFN branch on ``x`` (already scaled): (new x, routed)."""
    n = None
    if ffn == "mlp":
        x = x + _res(c, apply_mlp(c, sp["mlp"], apply_norm(c, sp["norm2"], x)))
    elif ffn == "moe":
        y, n, _ = moe_mod.moe_layer(c, sp["moe"],
                                    apply_norm(c, sp["norm2"], x), valid)
        x = x + _res(c, y)
    return x, n


def _total(ns: list):
    """Sum of a period's routed counts (None where a slot routes none)."""
    ns = [n for n in ns if n is not None]
    return sum(ns[1:], ns[0]) if ns else None


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _slot_init(key, c: ModelConfig, mixer: str, ffn: Optional[str],
               cross: bool = False) -> Params:
    ks = jax.random.split(key, 6)
    p: dict = {"norm1": norm_init(c)}
    if mixer == "attn":
        p["attn"] = attn.attn_init(ks[0], c)
    else:
        p["mamba"] = ssm_mod.mamba_init(ks[0], c)
    if cross:
        p["norm_x"] = norm_init(c)
        p["cross"] = attn.attn_init(ks[1], c)
    if ffn == "mlp":
        p["norm2"] = norm_init(c)
        p["mlp"] = mlp_init(ks[2], c, c.d_ff)
    elif ffn == "moe":
        p["norm2"] = norm_init(c)
        p["moe"] = moe_mod.moe_init(ks[3], c)
    return p


def stack_init(key, c: ModelConfig, cross: bool = False) -> Params:
    """Stacked layer params: leaf leading dim = n_periods."""
    period = period_of(c)
    n_periods = c.n_layers // period
    kinds = slot_kinds(c)

    def one_period(k):
        kslots = jax.random.split(k, period)
        return {f"slot{i}": _slot_init(kslots[i], c, *kinds[i], cross=cross)
                for i in range(period)}

    keys = jax.random.split(key, n_periods)
    periods = [one_period(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *periods)


def enc_stack_init(key, c: ModelConfig) -> Params:
    """Encoder stack (bidirectional attn + mlp), its own depth."""
    keys = jax.random.split(key, c.n_enc_layers)
    layers = [_slot_init(k, c, "attn", "mlp") for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


# ---------------------------------------------------------------------------
# Forward (training / full sequence)
# ---------------------------------------------------------------------------


def _apply_slot(c: ModelConfig, sp: Params, x: jax.Array, *, mixer: str,
                ffn: Optional[str], causal: bool, impl: str,
                positions=None, enc_kv=None, unroll: bool = False):
    rh = lambda t: attn._hint(t, "resid_spec")  # bf16 block all-reduce
    h = apply_norm(c, sp["norm1"], x)
    if mixer == "attn":
        h = attn.self_attention(c, sp["attn"], h, causal=causal,
                                positions=positions, impl=impl,
                                unroll=unroll)
    else:
        h = ssm_mod.mamba_forward(c, sp["mamba"], h, unroll=unroll)
    x = x + rh(_res(c, h))
    aux = jnp.zeros((), jnp.float32)
    if enc_kv is not None:
        h = apply_norm(c, sp["norm_x"], x)
        x = x + rh(attn.cross_attention(c, sp["cross"], h, enc_kv, impl=impl))
    if ffn == "mlp":
        x = x + rh(_res(c, apply_mlp(c, sp["mlp"],
                                     apply_norm(c, sp["norm2"], x))))
    elif ffn == "moe":
        y, _n, aux = moe_mod.moe_layer(c, sp["moe"],
                                       apply_norm(c, sp["norm2"], x))
        x = x + rh(_res(c, y))
    return x, aux


def stack_forward(c: ModelConfig, layers: Params, x: jax.Array, *,
                  causal: bool = True, impl: str = "repeat",
                  remat: str = "full", positions=None,
                  enc_kv_stacked=None, unroll: bool = False):
    """Run the full layer stack. x: (B, S, D) -> (B, S, D), aux_loss."""
    kinds = slot_kinds(c)

    def body(carry, inp):
        x, aux = carry
        if enc_kv_stacked is not None:
            period_params, ekv = inp
        else:
            period_params, ekv = inp, None
        for i, (mixer, ffn) in enumerate(kinds):
            x, a = _apply_slot(c, period_params[f"slot{i}"], x, mixer=mixer,
                               ffn=ffn, causal=causal, impl=impl,
                               positions=positions, unroll=unroll,
                               enc_kv=None if ekv is None else
                               (ekv[f"slot{i}"]["k"], ekv[f"slot{i}"]["v"]))
            aux = aux + a
        return (x, aux), None

    if remat == "full":
        body = jax.checkpoint(body, policy=None)  # recompute everything
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    xs = layers if enc_kv_stacked is None else (layers, enc_kv_stacked)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs,
                               unroll=unroll)
    return x, aux


# ---------------------------------------------------------------------------
# Prefill / decode (serving)
# ---------------------------------------------------------------------------


def stack_prefill(c: ModelConfig, layers: Params, x: jax.Array, *,
                  impl: str = "repeat", positions=None, enc_kv_stacked=None,
                  prefix_kv=None, paged_prefix=None, paged_tables=None,
                  paged_impl: str = "xla", paged_interpret: bool = False,
                  unroll: bool = False, valid_len=None, carry_slots=None):
    """Full-sequence causal pass that also emits per-layer caches.

    ``prefix_kv`` threads per-layer cached-prefix K/V (stacked like the
    caches tree: leading axis = n_periods, per-slot ``{"k","v"}`` of
    shape (B, T_pre, Kh, Dh)) into every attention slot — the suffix
    prefill of prefix caching. Attention-only stacks: the SSD
    recurrence/conv state of mamba mixers depends on the whole sequence
    and cannot skip the prefix.

    ``paged_prefix`` is the paged twin: the engine's pool cache tree
    itself (k/v leaves (n_periods, n_blocks, bs, Kh, Dh), plus
    k_scale/v_scale when int8) rides the scan as xs while the shared
    ``paged_tables`` (B, npre) addresses each row's prefix blocks —
    attention dispatches ``kernels.ops.paged_prefill_attention`` and the
    dense prefix KV is never gathered out of the pool. A stack with
    mamba mixers resumes there too: ``carry_slots`` (B,) int32 names the
    pool row whose conv and SSM state each row carries in (ids past the
    pool start from zeros) — the chunk after a block-aligned prefix.

    ``valid_len`` (B,) int32 counts each row's real tokens; the rest are
    padding, which mamba mixers step over and expert layers route
    nowhere. Returns (x, caches, routed): ``routed`` counts the real
    tokens' choices that the dropless expert layers computed here (None
    when the stack has none).
    """
    kinds = slot_kinds(c)
    assert enc_kv_stacked is None or prefix_kv is None
    assert prefix_kv is None or paged_prefix is None
    valid = None
    if valid_len is not None:
        valid = jnp.arange(x.shape[1])[None, :] < valid_len[:, None]

    def body(carry, inp):
        x = carry
        ekv = pkv = ppx = None
        if enc_kv_stacked is not None:
            period_params, ekv = inp
        elif prefix_kv is not None:
            period_params, pkv = inp
        elif paged_prefix is not None:
            period_params, ppx = inp
        else:
            period_params = inp
        caches, routed = {}, []
        for i, (mixer, ffn) in enumerate(kinds):
            sp = period_params[f"slot{i}"]
            h = apply_norm(c, sp["norm1"], x)
            if mixer == "attn":
                pp = None
                if ppx is not None:
                    d = ppx[f"slot{i}"]
                    pp = (d["k"], d["v"], d.get("k_scale"), d.get("v_scale"),
                          paged_tables, paged_impl, paged_interpret)
                h, (k, v) = attn.prefill_attention(
                    c, sp["attn"], h, positions=positions,
                    impl=impl, unroll=unroll,
                    prefix_kv=None if pkv is None else
                    (pkv[f"slot{i}"]["k"], pkv[f"slot{i}"]["v"]),
                    paged_prefix=pp)
                caches[f"slot{i}"] = {"k": k, "v": v}
            else:
                assert pkv is None and (ppx is None or carry_slots is not None), (
                    "a mamba mixer resumes only from its carried state")
                h0 = conv0 = None
                if ppx is not None:
                    st = ppx[f"slot{i}"]
                    h0, conv0 = (jnp.take(st[n], carry_slots, axis=0,
                                          mode="fill", fill_value=0)
                                 for n in ("ssm", "conv"))
                h, (conv_tail, hstate) = ssm_mod.mamba_forward(
                    c, sp["mamba"], h, h0=h0, conv0=conv0,
                    lengths=valid_len, return_state=True, unroll=unroll)
                caches[f"slot{i}"] = {"ssm": hstate, "conv": conv_tail}
            x = x + _res(c, h)
            if ekv is not None:
                hx = apply_norm(c, sp["norm_x"], x)
                x = x + attn.cross_attention(
                    c, sp["cross"], hx,
                    (ekv[f"slot{i}"]["k"], ekv[f"slot{i}"]["v"]), impl=impl)
            x, n = _ffn(c, sp, ffn, x, valid)
            routed.append(n)
        n = _total(routed)
        return x, (caches if n is None else (caches, n))

    if enc_kv_stacked is not None:
        xs = (layers, enc_kv_stacked)
    elif prefix_kv is not None:
        xs = (layers, prefix_kv)
    elif paged_prefix is not None:
        xs = (layers, paged_prefix)
    else:
        xs = layers
    x, ys = jax.lax.scan(body, x, xs, unroll=unroll)
    if routes_share(c):
        caches, n = ys
        return x, caches, jnp.sum(n)
    return x, ys, None


def stack_decode(c: ModelConfig, layers: Params, x: jax.Array, caches: Params,
                 pos: jax.Array, *, impl: str = "grouped",
                 enc_kv_stacked=None, unroll: bool = False,
                 block_tables=None, n_kv_blocks: Optional[int] = None,
                 paged_impl: str = "xla", paged_interpret: bool = False,
                 live=None):
    """One-token decode through the stack, updating caches in place.

    ``block_tables`` selects the paged KV path: attention k/v cache
    leaves are shared block pools and every layer reads the same
    ``(B, max_blocks)`` table (see ``attention.decode_attention``). The
    stacked pools (k, v and an int8 pool's k_scale/v_scale, leading axis
    n_periods) ride the layer scan as its carry, and each layer writes
    its token into them at the scanned layer index: sliced out as xs and
    stacked back as ys, every pool would be copied whole each step.
    SSM/conv state leaves stay per-slot rows, scanned as xs/ys, in either
    layout.

    ``live`` (B,) bool marks the rows that decode a real token: the other
    rows keep their SSM/conv state (a slot between prefill chunks carries
    it) and route nowhere. Returns (x, caches, routed) as
    :func:`stack_prefill` does.
    """
    kinds = slot_kinds(c)
    pooled = {f"slot{i}" for i, (mixer, _) in enumerate(kinds)
              if block_tables is not None and mixer == "attn"}
    pools = {n: caches[n] for n in pooled}
    rows = {n: v for n, v in caches.items() if n not in pooled}
    layer_ids = None
    if pooled:
        layer_ids = jnp.arange(jax.tree.leaves(layers)[0].shape[0],
                               dtype=jnp.int32)

    def keep(new, old):
        if live is None:
            return new
        m = live.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old.astype(new.dtype))

    valid = None if live is None else live[:, None]

    def body(carry, inp):
        x, pools = carry
        period_params, cache, ekv, layer = inp
        new_pools, new_rows, routed = {}, {}, []
        for i, (mixer, ffn) in enumerate(kinds):
            name = f"slot{i}"
            sp = period_params[name]
            h = apply_norm(c, sp["norm1"], x)
            if mixer == "attn":
                sc = pools[name] if name in pools else cache[name]
                h, *kv = attn.decode_attention(
                    c, sp["attn"], h, sc["k"], sc["v"], pos, impl=impl,
                    block_tables=block_tables, n_kv_blocks=n_kv_blocks,
                    paged_impl=paged_impl, paged_interpret=paged_interpret,
                    cache_k_scale=sc.get("k_scale"),
                    cache_v_scale=sc.get("v_scale"), layer=layer)
                out = new_pools if name in pools else new_rows
                out[name] = dict(zip(("k", "v", "k_scale", "v_scale"), kv))
            else:
                sc = cache[name]
                h, conv_s, ssm_s = ssm_mod.mamba_decode(c, sp["mamba"], h,
                                                        sc["conv"], sc["ssm"])
                new_rows[name] = {"conv": keep(conv_s, sc["conv"]),
                                  "ssm": keep(ssm_s, sc["ssm"])}
            x = x + _res(c, h)
            if ekv is not None:
                hx = apply_norm(c, sp["norm_x"], x)
                x = x + attn.cross_attention(
                    c, sp["cross"], hx,
                    (ekv[name]["k"], ekv[name]["v"]), impl=impl)
            x, n = _ffn(c, sp, ffn, x, valid)
            routed.append(n)
        n = _total(routed)
        return (x, new_pools), (new_rows if n is None else (new_rows, n))

    (x, pools), ys = jax.lax.scan(
        body, (x, pools), (layers, rows, enc_kv_stacked, layer_ids),
        unroll=unroll)
    n = None
    if routes_share(c):
        ys, n = ys
        n = jnp.sum(n)
    return x, {**ys, **pools}, n
