"""MoE: scatter dispatch vs dense oracle; capacity semantics; router."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe


def _cfg(**kw):
    c = get_config("granite-moe-3b-a800m").reduced()
    return dataclasses.replace(c, **kw) if kw else c


def test_dispatch_matches_dense_oracle():
    # capacity_factor high enough that nothing drops
    c = _cfg(capacity_factor=8.0)
    key = jax.random.key(0)
    p = moe.moe_init(key, c)
    x = jax.random.normal(jax.random.key(1), (2, 32, c.d_model),
                          jnp.float32)
    y_fast, aux_f = moe.moe_forward(c, p, x)
    y_ref, aux_r = moe.moe_forward_dense(c, p, x)
    np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(aux_f), float(aux_r), rtol=1e-5)


def test_capacity_drops_bounded():
    """With tiny capacity, outputs are a subset (dropped tokens -> residual
    contribution zero), never garbage."""
    c = _cfg(capacity_factor=0.25)
    p = moe.moe_init(jax.random.key(0), c)
    x = jax.random.normal(jax.random.key(1), (2, 64, c.d_model), jnp.float32)
    y, _ = moe.moe_forward(c, p, x)
    assert bool(jnp.all(jnp.isfinite(y)))
    # dropped-token rows are exactly zero (before shared expert)
    if not c.shared_d_ff:
        norms = jnp.linalg.norm(y.reshape(-1, c.d_model), axis=-1)
        assert float((norms == 0).mean()) > 0  # something dropped


def test_router_topk_normalized():
    c = _cfg()
    p = moe.moe_init(jax.random.key(0), c)
    x = jax.random.normal(jax.random.key(2), (16, c.d_model), jnp.float32)
    w, e, aux = moe.router_topk(c, p, x)
    assert w.shape == (16, c.top_k)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert int(e.max()) < c.n_experts
    assert float(aux) >= 1.0 - 1e-3  # aux >= 1 with equality at balance


def test_shared_expert_added():
    c = _cfg(shared_d_ff=64, capacity_factor=8.0)
    p = moe.moe_init(jax.random.key(0), c)
    x = jax.random.normal(jax.random.key(1), (1, 16, c.d_model), jnp.float32)
    y_with, _ = moe.moe_forward(c, p, x)
    p_no = dict(p)
    c_no = dataclasses.replace(c, shared_d_ff=0)
    y_without, _ = moe.moe_forward(c_no, p_no, x)
    assert float(jnp.abs(y_with - y_without).max()) > 1e-6


def test_expert_capacity_formula():
    c = _cfg(capacity_factor=1.25)
    cap = moe.expert_capacity(c, 1024)
    assert cap >= 1024 * c.top_k * 1.25 / c.n_experts - 1
    assert moe.expert_capacity(c, 4) >= 4  # floor
