"""The per-layer readers: their arithmetic on hand-made facts, and
nothing (never 0) where the trace holds nothing to read."""
import json
import pathlib

import pytest

from perfbench import run as R

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CFG = json.loads((ROOT / "perfbench/configs/granite-8b-l9.json").read_text())
MIX = json.loads((ROOT / "perfbench/mixes/chat.json").read_text())
GPT = json.loads((ROOT / "perfbench/configs/gpt-800m.json").read_text())
TRAIN = json.loads((ROOT / "perfbench/mixes/train_s2048_b2.json").read_text())


def reader(name):
    return R.load_module(R.HERE / "layers" / f"{name}.py").read


def facts(ops=None, modules=None, busy=0.5):
    req = {"prompt": [0] * 600, "tokens": [1] * 5, "want": 5,
           "reason": "length", "arrival": 0.0, "admitted": 0.25,
           "first": 0.5, "finish": 1.0}
    return {"results": [req, dict(req, admitted=0.05)],
            "steps": [("prefill", 0, 1, 1, 1, 1), ("decode", 0, 1, 8, 8, 4),
                      ("decode", 1, 2, 2, 2, 1)],
            "trace": {"ops": ops or {}, "modules": modules or {},
                      "busy_s": busy, "device_ops": [[]]},
            "window_s": 2.0, "chips": 1, "tokens": 4096,
            "device": {"kind": "TPU v5 lite"}}


def test_every_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        assert callable(reader(m["name"]))


def test_readers_find_nothing_without_a_trace_of_it():
    f = facts()
    for name in ("paged_prefill_roofline", "mfu.prefill_step"):
        assert reader(name)(f, CFG, MIX) is None


def test_scheduler_counts():
    f = facts()
    assert reader("sched.queue_wait_p50_ms")(f, CFG, MIX) == pytest.approx(50)


def test_idle_share():
    assert reader("device_idle.chat")(facts(busy=0.5), CFG, MIX) == 75.0


def test_prefill_roofline_counts_chunks_after_the_first():
    f = facts(ops={"jit_prefill_hit:custom-call": 1e-3})
    # a 600-token prompt in 256-token chunks: (256, 256) and (512, 88)
    pairs = 256 * 256 + 256 * 257 / 2 + 88 * 512 + 88 * 89 / 2
    flops = 2 * 9 * 4 * 32 * 128 * pairs
    nbytes = 2 * 9 * 2 * (2 * 8 * 128 * (512 + 600) + 32 * 128 * (256 + 88) * 2)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 1e-3
    assert reader("paged_prefill_roofline")(f, CFG, MIX) == pytest.approx(want)


def test_train_mfu():
    from perfbench.counts import lm_flops
    per_token = lm_flops.train_flops_per_token(GPT, 2048)
    # 6N = 4.54 GFLOP plus 12 L H Dh S = 0.91 GFLOP
    assert per_token == pytest.approx(5.45e9, rel=0.01)
    got = reader("mfu.train")(facts(), GPT, TRAIN)
    assert got == pytest.approx(100 * per_token * 4096 / (2.0 * 197e12))
