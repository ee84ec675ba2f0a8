"""The hybrid cell's per-layer readers and counts: their arithmetic on
hand-made spans and traces, nothing (never 0) where the run recorded
nothing to read, and the counts tied to the program's own shapes."""
import json

import bench_helpers  # noqa: F401 (puts the program on the path)
import pytest
from hsmall_helpers import FILE, ROOT, TINY, tiny_model_config

from perfbench import counters, spans
from perfbench import run as R
from perfbench.counts import hybrid_decode, hybrid_flops

MIX = json.loads((ROOT / "perfbench/mixes/chat_hsmall.json").read_text())


def reader(name):
    return R.load_module(R.HERE / "layers" / f"{name}.py").read


def facts(modules=None, prompts=(600,)):
    done = [{"prompt": [0] * p, "tokens": [1] * 5, "want": 5,
             "reason": "length"} for p in prompts]
    return {"results": done,
            "trace": {"ops": {}, "modules": modules or {}, "busy_s": 0.5,
                      "device_ops": [[]]},
            "window_s": 2.0, "chips": 1, "device": {"kind": "TPU v5 lite"}}


def emit(**kw):
    return ("serve.emit", 0.0, 1.0, kw)


# -- counts against the program -------------------------------------------


@pytest.mark.parametrize("cfg", [FILE, TINY], ids=["cell", "tiny"])
def test_weight_bytes_are_the_program_s(cfg):
    import jax
    from repro.models import lm
    from perfbench.model import model_config
    c = model_config(cfg) if cfg is FILE else tiny_model_config(cfg)
    want = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(lm.init_abstract(c)))
    assert hybrid_decode.weight_bytes(cfg) == want


def test_state_and_kv_bytes_are_the_program_s():
    import jax
    from repro.models import lm
    from repro.serve.cache import abstract_caches
    c = tiny_model_config(TINY)
    (caches, _), _ = abstract_caches(c, 3, 64, lm.init_abstract(c))
    rows = sum(x.size * x.dtype.itemsize for path, x in
               jax.tree_util.tree_flatten_with_path(caches)[0]
               if path[-1].key in ("conv", "ssm"))
    assert hybrid_decode.state_bytes(TINY, 3) == 2 * rows
    kv = sum(x.size * x.dtype.itemsize for path, x in
             jax.tree_util.tree_flatten_with_path(caches)[0]
             if path[-1].key in ("k", "v"))
    # 3 rows of 64 positions are 12 blocks of 16
    assert hybrid_decode.kv_block_bytes(TINY, 16) * 12 == kv


def test_prompt_flops_by_hand():
    cfg = dict(TINY, hidden_size=4, num_attention_heads=2,
               num_key_value_heads=1, head_dim=2, intermediate_size=3,
               shared_intermediate_size=5, vocab_size=7, mamba_n_heads=2,
               mamba_d_head=2, mamba_d_state=3, mamba_d_conv=2,
               published={"num_local_experts": 6})
    # mamba a token: in_proj 2*4*(8+6+2) + conv 2*2*(4+6) + out 2*4*4 +
    # state 4*2*2*3; attention a token: 2*4*4*2 + 2*2*2*4; every layer:
    # router 2*4*6 + shared 6*4*5
    mamba, attn, every = 128 + 40 + 32 + 48, 64 + 32, 48 + 120
    # 5 tokens in chunks of 4: 10 + 1 causal pairs of SSD, 15 keys
    ssd = 2 * 11 * (3 + 4)
    keys = 4 * 2 * 2 * 15
    head = 2 * 4 * 256
    want = 3 * (5 * mamba + ssd) + (5 * attn + keys) + 4 * 5 * every + head
    assert hybrid_flops.prompt_flops(cfg, 5, chunk=4) == want
    assert hybrid_flops.routed_flops(cfg, 10) == 6 * 4 * 3 * 10


# -- readers ----------------------------------------------------------------


def test_group_fill(monkeypatch):
    evs = [("serve.prefill", 0, 1, {"moe_rows": 300}),
           ("serve.decode", 0, 1, {"moe_rows": 100}),
           ("serve.decode", 0, 1, {"k": 1}), emit(moe_local=30),
           emit(moe_local=20, moe_local_prefill=5), emit()]
    monkeypatch.setattr(counters, "events", lambda: evs)
    assert reader("moe.group_fill")(facts(), FILE, MIX) == pytest.approx(12.5)


def test_prefill_mfu(monkeypatch):
    monkeypatch.setattr(counters, "events", lambda: [
        emit(moe_local=99, moe_local_prefill=400),
        emit(moe_local=1, moe_local_prefill=600)])
    f = facts({"jit_serve_prefill": 0.5, "jit_prefill_hit": 1.5,
               "jit_step": 9.0}, prompts=(600, 1000))
    flops = (hybrid_flops.prompt_flops(FILE, 600)
             + hybrid_flops.prompt_flops(FILE, 1000)
             + hybrid_flops.routed_flops(FILE, 1000))
    assert reader("hybrid.mfu.prefill")(f, FILE, MIX) == pytest.approx(
        100 * flops / (2.0 * 197e12))


def test_decode_roofline(monkeypatch):
    calls = [{"kind": "decode", "rows": 16, "used": 40, "n_steps": 4},
             {"kind": "decode", "rows": 16, "used": 10, "n_steps": 1},
             {"kind": "prefill", "rows": 16, "used": 600, "n_steps": 1}]
    monkeypatch.setattr(spans, "engine_calls", lambda: calls)
    nbytes = (5 * (hybrid_decode.weight_bytes(FILE)
                   + hybrid_decode.state_bytes(FILE, 16))
              + 50 * hybrid_decode.kv_block_bytes(FILE, 16))
    got = reader("hybrid.decode_roofline")(facts({"jit_step": 0.1}), FILE, MIX)
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.1)
    # about 7.7 GB a micro-step
    assert 7.5e9 < hybrid_decode.least_bytes(FILE, 16, 16, 1, 0) < 7.9e9


@pytest.mark.parametrize("name", ["moe.group_fill", "hybrid.mfu.prefill",
                                  "hybrid.decode_roofline"])
def test_nothing_recorded_reads_nothing(monkeypatch, name):
    """A program without the hybrid engine's counters (the parent's)
    reads None: no kwargs, no decode calls, no program time."""
    monkeypatch.setattr(counters, "events",
                        lambda: [("serve.emit", 0, 1, {})])
    monkeypatch.setattr(spans, "engine_calls", lambda: [])
    f = facts({"jit_serve_prefill": 0.5, "jit_step": 1.0})
    assert reader(name)(f, FILE, MIX) is None


def test_counters_without_a_profile_read_nothing(monkeypatch, tmp_path):
    monkeypatch.setattr(R, "TRACE_DIR", tmp_path)
    assert counters.events() == []
    assert counters.total([], ("serve.emit",), "moe_local") is None


def test_counters_read_the_engine_s_profile(tmp_path):
    """The hybrid engine's kwargs reach a real profile's host events:
    three prompts of 40, 70 and 20 tokens in 32-token chunks (3 chunks
    resume a state), and fewer routed choices than buffer rows."""
    import glob

    import jax
    import numpy as np
    from repro.models import lm
    from repro.serve.engine import ServeEngine
    from repro.serve.requests import Request

    from perfbench.model import reference_of
    c = tiny_model_config(TINY)
    params = reference_of(TINY).make_weights(lm.init_abstract(c), 3)
    eng = ServeEngine(c, params, cache="paged", sched="chunked", n_slots=4,
                      max_len=128, block_size=16, chunk_tokens=32)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 512, n, np.int32),
                    max_new_tokens=6) for i, n in enumerate([40, 70, 20])]
    eng.serve(reqs)                                  # compiles
    jax.profiler.start_trace(str(tmp_path))
    eng.serve(reqs)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    evs = spans.host_events(path)
    local = counters.total(evs, ("serve.emit",), "moe_local")
    part = counters.total(evs, ("serve.emit",), "moe_local_prefill")
    rows = counters.total(evs, ("serve.prefill", "serve.decode"), "moe_rows")
    assert counters.total(evs, ("serve.prefill",), "carried") == 3
    assert 0 < part < local < rows
