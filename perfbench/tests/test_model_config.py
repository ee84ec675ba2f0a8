"""``model.model_config`` holds the program to the Mamba-2 and routing
widths a configuration file states, and skips those it does not state."""
import dataclasses

import bench_helpers  # noqa: F401 (puts the program on the path)
import pytest

from perfbench.model import model_config

#: a tiny hybrid configuration (Mamba-2 with attention every fourth
#: layer, routed experts) in the widths the file below states
TINY_HYBRID = {
    "name": "tiny-hybrid", "arch": "tiny-hybrid", "num_hidden_layers": 8,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "dtype": "bfloat16", "mamba_d_state": 16, "mamba_d_head": 16,
    "mamba_n_heads": 8, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 32, "mamba_n_groups": 1, "num_experts_per_tok": 2}


@pytest.fixture
def tiny_hybrid(monkeypatch):
    from repro.configs import REGISTRY, get_config
    c = get_config("jamba-v0.1-52b").reduced(name="tiny-hybrid")
    monkeypatch.setitem(REGISTRY, "tiny-hybrid", c)
    return c


def test_stated_hybrid_widths_are_the_program_s(tiny_hybrid):
    c = model_config(TINY_HYBRID)
    assert c == dataclasses.replace(tiny_hybrid, n_layers=8)
    assert (c.family, c.ssm_nheads, c.top_k) == ("hybrid", 8, 2)


@pytest.mark.parametrize("key, value", [
    ("mamba_d_state", 128), ("mamba_d_head", 64), ("mamba_n_heads", 128),
    ("mamba_d_conv", 3), ("mamba_expand", 1), ("mamba_chunk_size", 256),
    ("num_experts_per_tok", 10)])
def test_a_stated_width_that_disagrees_is_refused(tiny_hybrid, key, value):
    with pytest.raises(ValueError, match="the file states"):
        model_config(dict(TINY_HYBRID, **{key: value}))


def test_more_than_one_mamba_group_is_refused(tiny_hybrid):
    with pytest.raises(ValueError, match="mamba_n_groups"):
        model_config(dict(TINY_HYBRID, mamba_n_groups=8))


def test_unstated_widths_are_not_compared(tiny_hybrid):
    stated = {k: v for k, v in TINY_HYBRID.items()
              if not k.startswith("mamba_") and k != "num_experts_per_tok"}
    assert model_config(stated) == model_config(TINY_HYBRID)
