"""The plain reference computes the model the program runs: at a tiny
size in float32 the program's forward pass and the reference's logits
agree to rounding, on the benchmark's own weights in the program's
layout."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_helpers import TINY, tiny_model_config

from perfbench.reference import model as ref


@pytest.mark.parametrize("kind", ["gpt", "granite"])
def test_reference_matches_the_program_in_float32(kind):
    from repro.models import lm
    cfg = TINY[kind]
    c = dataclasses.replace(tiny_model_config(cfg), dtype="float32",
                            param_dtype="float32")
    abstract = lm.init_abstract(c)
    w = ref.make_weights(abstract, 2**32 + 3)
    assert jax.tree.structure(w) == jax.tree.structure(abstract)
    toks = jax.random.randint(jax.random.key(1), (2, 48), 0, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want, _ = lm.forward(c, w, toks, remat="none")
    want = np.asarray(want)[..., :cfg["vocab_size"]]
    got = np.asarray(ref.logits_of(cfg, w, ref.hidden(cfg, w, toks, q_block=16)))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    one = np.asarray(ref.sequence_logits(cfg, w, toks[:1]))
    np.testing.assert_allclose(one[0], want[0], atol=2e-4, rtol=2e-4)


def test_fp8_control_departs_from_float32():
    cfg = TINY["granite"]
    from repro.models import lm
    w = ref.make_weights(lm.init_abstract(tiny_model_config(cfg)), 7)
    toks = jnp.arange(32)[None] % cfg["vocab_size"]
    a = np.asarray(ref.sequence_logits(cfg, w, toks))
    b = np.asarray(ref.sequence_logits(cfg, w, toks, "fp8"))
    assert 1e-3 < np.abs(a - b).max() < 1.0


def test_seeds_beyond_32_bits_differ():
    k1, k2 = ref.seed_key(5), ref.seed_key(5 + 2**31)
    assert not np.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))
