"""The hybrid serve cell (``serve.granite-4.0-h-small-l10.chat``) at CPU
sizes: a tiny Granite 4.0-H (one period of four layers: Mamba-2,
attention, Mamba-2, Mamba-2; 2 of 8 experts held, 3 a token; the
published multipliers) stated as the configuration file states it, and
the program's config made from that statement."""
from __future__ import annotations

import dataclasses
import json
import pathlib

import bench_helpers  # noqa: F401 (puts the program on the path)

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "serve.granite-4.0-h-small-l10.chat"
FILE = json.loads(
    (ROOT / "perfbench/configs/granite-4.0-h-small-l10.json").read_text())

#: the configuration file's keys at a tiny size
TINY = dict(
    FILE, name="tiny-granite-h", num_hidden_layers=4,
    layer_types=["mamba", "attention", "mamba", "mamba"], hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=32, shared_intermediate_size=48, vocab_size=512,
    num_local_experts=2, num_experts_per_tok=3, mamba_n_heads=8,
    mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=32,
    published={"num_hidden_layers": 4, "num_local_experts": 8})


def tiny_model_config(cfg: dict):
    """The program's config for a file in ``TINY``'s keys."""
    from repro.configs import get_config
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return dataclasses.replace(
        get_config(cfg["arch"]), n_layers=cfg["num_hidden_layers"],
        attn_layer_period=cfg["num_hidden_layers"],
        attn_layer_offset=kinds.index("attention"),
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], expert_d_ff=cfg["intermediate_size"],
        shared_d_ff=cfg["shared_intermediate_size"], vocab=cfg["vocab_size"],
        n_experts=cfg["published"]["num_local_experts"],
        n_experts_held=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"], ssm_state=cfg["mamba_d_state"],
        ssm_headdim=cfg["mamba_d_head"], ssm_chunk=cfg["mamba_chunk_size"],
        ssm_expand=cfg["mamba_n_heads"] * cfg["mamba_d_head"]
        // cfg["hidden_size"])


def shrink(files, driver, prompt=None, **config):
    """The cell's run at a CPU size: the tiny model (``config`` overrides
    its keys), prompts of up to three 32-token chunks (``prompt``
    overrides their distribution's keys), four slots, a high rate."""
    files["config"] = dict(TINY, **config)
    mix = files["mix"]
    files["mix"] = dict(
        mix, arrivals={"kind": "poisson", "rate_per_s": 8.0},
        prompt={**mix["prompt"], "median": 48, "min": 16, "max": 96,
                "round_up": 16, **(prompt or {})},
        output=dict(mix["output"], median=8, min=4, max=24),
        engine={"n_slots": 4, "block_size": 16, "chunk_tokens": 32,
                "max_len": 128},
        check={"sample": 3})
    driver.model_config = tiny_model_config


#: the configuration's Mamba-2 and attention head widths (64 and 128,
#: d_state 128) at d 1024, with a vocabulary of 8,192
WIDE = dict(hidden_size=1024, num_attention_heads=8, num_key_value_heads=2,
            head_dim=128, mamba_n_heads=32, mamba_d_head=64,
            mamba_d_state=128, vocab_size=8192, intermediate_size=256,
            shared_intermediate_size=512)


def shrink_wide(files, driver):
    """The cell at ``WIDE`` widths, some tens of served tokens a request
    and eight requests checked: the smallest size at which the fp8
    control, and a state zeroed where a whole chunk follows it, read above
    the cell's logit-gap limit on the CPU (at ``TINY`` they read 7e-4 to
    1.4e-3 and 5e-4)."""
    shrink(files, driver, **WIDE)
    mix = files["mix"]
    files["mix"] = dict(mix, output=dict(mix["output"], median=24, min=16,
                                         max=48),
                        engine=dict(mix["engine"], max_len=160),
                        check={"sample": 8})
