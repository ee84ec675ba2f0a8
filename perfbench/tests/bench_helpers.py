"""Shared helpers and fixtures: tiny configurations of the cells' models and a
stand-in for the run context, so that the drivers run on the CPU."""
from __future__ import annotations

import dataclasses
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "gpt": {"name": "tiny-gpt", "arch": "gpt-800m", "num_hidden_layers": 2,
            "hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 4, "head_dim": 16,
            "intermediate_size": 128, "vocab_size": 500,
            "hidden_act": "gelu", "norm": "layernorm", "rope_theta": 10000.0,
            "tie_word_embeddings": True, "dtype": "bfloat16"},
    "granite": {"name": "tiny-granite", "arch": "granite-8b",
                "num_hidden_layers": 2, "hidden_size": 64,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
                "hidden_act": "silu", "norm": "rmsnorm",
                "rope_theta": 10000000.0, "tie_word_embeddings": False,
                "dtype": "bfloat16"},
}


def tiny_model_config(cfg: dict):
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(cfg["arch"]), n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"])


class FakeCtx:
    """The run context without the look for a chip, the compile cache or
    the profiler."""

    def __init__(self, cfg, mix, limits, seed=3, seconds=0.5, chips=1):
        import time
        self.cfg, self.mix, self.limits = cfg, mix, limits
        self.seed, self.seconds, self.trace, self.chips = seed, seconds, False, chips
        self.t_start = time.perf_counter()
        self.window_start = None
        self.memory_peak_bytes = None
        self.notes = {}

    def span(self, name):
        import contextlib
        return contextlib.nullcontext()

    def open_window(self):
        import time
        self.window_start = time.perf_counter()
        return self.window_start

    def close_window(self):
        import time
        return time.perf_counter()

    def read_memory(self, devices=None):
        return None


def shrink_train(files, driver, **mix):
    """The training cell's run at a CPU size: the tiny GPT, short rows."""
    files["config"] = dict(TINY["gpt"])
    files["mix"] = dict(files["mix"], seq_len=32, row_pool_steps=8,
                        trace_steps=2, **mix)
    driver.model_config = tiny_model_config


def shrink_serve(files, driver):
    """A serve cell's run at a CPU size: the tiny Granite, short prompts,
    four slots, a high rate."""
    files["config"] = dict(TINY["granite"])
    mix = files["mix"]
    files["mix"] = dict(
        mix, arrivals={"kind": "poisson", "rate_per_s": 8.0},
        prompt=dict(mix["prompt"], median=48, min=16, max=96, round_up=16),
        output=dict(mix["output"], median=8, min=4, max=24),
        engine={"n_slots": 4, "block_size": 16, "chunk_tokens": 32,
                "max_len": 128},
        check={"sample": 3})
    driver.model_config = tiny_model_config


def shrink_train_wide(files, driver):
    """The training cell at the width of its configuration (d 1536, 16
    heads of 96, FFN 6144) and two layers: the smallest size at which the
    fp8 control reads above the cell's loss limit on the CPU."""
    shrink_train(files, driver)
    files["config"] = dict(TINY["gpt"], hidden_size=1536,
                           num_attention_heads=16, num_key_value_heads=16,
                           head_dim=96, intermediate_size=6144)


def shrink_serve_wide(files, driver):
    """A serve cell at half its configuration's width (d 2048, 16/4 heads
    of 128, FFN 7168), two layers, and some tens of served tokens a
    request: the smallest size at which the fp8 control reads above the
    cell's logit-gap limit on the CPU."""
    shrink_serve(files, driver)
    mix = files["mix"]
    files["config"] = dict(TINY["granite"], hidden_size=2048,
                           num_attention_heads=16, num_key_value_heads=4,
                           head_dim=128, intermediate_size=7168)
    files["mix"] = dict(mix, output=dict(mix["output"], median=24, min=16,
                                         max=48),
                        engine=dict(mix["engine"], max_len=144),
                        check={"sample": 8})


def no_chip_check(chips):
    import jax
    return {"platform": jax.devices()[0].platform, "kind": "cpu",
            "count": len(jax.devices())}


@pytest.fixture
def cpu_run(monkeypatch):
    """``run.run_cell`` without the look for a chip and without a compile
    cache on disk."""
    from perfbench import run as R
    monkeypatch.setattr(R, "setup_jax", lambda: None)

    def go(workload, prepare, seconds=0.3, seed=5, trace=False):
        return R.run_cell(workload, seed, seconds, trace,
                          check_chip=no_chip_check, prepare=prepare)
    return go
