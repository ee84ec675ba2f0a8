"""``granite-4.0-h-small-l10.json`` states what the program runs: every
key that ``model.model_config`` does not compare (the shared expert's
width, the experts held and published, the four multipliers, the
positions choice, the layer pattern) is tied here to the program's
config, and the stated parameter count to the weights it holds."""
import dataclasses
import json

import bench_helpers  # noqa: F401 (puts the program on the path)
from hsmall_helpers import FILE, ROOT

from perfbench.model import model_config


def test_the_file_states_the_program_s_config():
    c = model_config(FILE)
    assert c.family == "hybrid" and c.n_layers == FILE["num_hidden_layers"]
    assert c.shared_d_ff == FILE["shared_intermediate_size"]
    assert c.expert_d_ff == c.d_ff == FILE["intermediate_size"]
    assert c.experts_held == FILE["num_local_experts"] == 18
    assert c.n_experts == FILE["published"]["num_local_experts"] == 72
    assert c.moe_dispatch == "dropless"
    assert (c.embed_mult, c.resid_mult, c.attn_mult, c.logits_div) == (
        FILE["embedding_multiplier"], FILE["residual_multiplier"],
        FILE["attention_multiplier"], FILE["logits_scaling"])
    assert FILE["position_embedding_type"] == "nope" and c.positions == "none"
    kinds = ["attention" if c.is_attn_layer(i) else "mamba"
             for i in range(c.n_layers)]
    assert kinds == FILE["layer_types"][:c.n_layers]
    assert c.ssm_conv == FILE["mamba_d_conv"] and FILE["mamba_conv_bias"]
    assert not FILE["mamba_proj_bias"] and not FILE["attention_bias"]
    assert c.tie_embeddings and FILE["tie_word_embeddings"]


def test_the_published_model_is_the_cut_one_uncut():
    from repro.configs import get_config
    whole = get_config("granite-4.0-h-small")
    c = model_config(FILE)
    assert whole.n_layers == FILE["published"]["num_hidden_layers"] == 40
    assert dataclasses.replace(whole, n_layers=c.n_layers,
                               n_experts_held=c.n_experts_held,
                               name=c.name) == c
    kinds = ["attention" if whole.is_attn_layer(i) else "mamba"
             for i in range(whole.n_layers)]
    assert kinds == FILE["layer_types"]


def test_param_count_is_the_weights_held():
    import jax
    from repro.models import lm
    c = model_config(FILE)
    held = sum(x.size for x in jax.tree.leaves(lm.init_abstract(c)))
    assert held == c.param_count() == FILE["param_count"]


def test_reduced_names_the_cut_and_the_benchmark_agrees():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(x for x in bench["configs"] if x["name"] == FILE["name"])
    assert FILE["reduced"] == entry["reduced"] == ["num_hidden_layers",
                                                   "num_local_experts"]
    assert set(FILE["published"]) == set(FILE["reduced"])
