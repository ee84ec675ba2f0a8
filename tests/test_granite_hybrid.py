"""Granite 4.0-H's mechanisms at a tiny size on the CPU: a Mamba-2 mixer
that resumes from carried state and steps over padding, the dropless
expert layer that holds a share of the experts, NoPE, the multipliers,
and the chunked paged engine serving the hybrid stack against the plain
reference (``perfbench/reference/granite_hybrid.py``).

Everything runs in float32, so the program and the reference agree to
rounding and greedy argmax does not flake on bf16 ties.
"""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm, moe, ssm
from repro.serve.engine import ServeEngine
from repro.serve.requests import Request

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: one period of four layers (mamba, attention, mamba, mamba), each with
#: 2 of 8 experts held (top-3) and a shared expert, and the published
#: multipliers
TINY = dataclasses.replace(
    get_config("granite-4.0-h-small-ep4"), name="tiny-granite-h",
    n_layers=4, attn_layer_period=4, attn_layer_offset=1, d_model=64,
    n_heads=4, n_kv_heads=2, d_head=16, d_ff=32, expert_d_ff=32,
    shared_d_ff=48, n_experts=8, top_k=3, n_experts_held=2, vocab=512,
    ssm_state=16, ssm_headdim=16, ssm_chunk=32, dtype="float32",
    param_dtype="float32")

#: the same model as a configuration file states it, for the reference
TINY_FILE = {
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "num_local_experts": 2, "num_experts_per_tok": 3,
    "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16, "rms_norm_eps": 1e-5,
    "vocab_size": 512}

#: served tokens against the float32 reference: the reference's best
#: logit minus the served token's. Both run in float32 and differ only in
#: summation order (the chunked SSD against the recurrence, the paged
#: kernels' online softmax), ~1e-6 on these logits; a wrong state or
#: token would cost at least the logits' spread (~1e-2).
GAP_TOL = 1e-4


def _ref():
    from perfbench.model import reference_of
    return reference_of({"name": "tiny", "reference": "granite_hybrid"})


@pytest.fixture(scope="module")
def params():
    return _ref().make_weights(lm.init_abstract(TINY), 11)


# ---------------------------------------------------------------------------
# Mamba-2: resume and padding
# ---------------------------------------------------------------------------


def _mamba_params(params):
    return jax.tree.map(lambda t: t[0], params["layers"]["slot0"]["mamba"])


def test_mamba_chunks_with_carried_state_equal_one_pass(params):
    p = _mamba_params(params)
    x = jax.random.normal(jax.random.key(2), (2, 96, TINY.d_model))
    y, (tail, h) = ssm.mamba_forward(TINY, p, x, return_state=True)
    ys, conv, hs = [], None, None
    for a, b in ((0, 32), (32, 48), (48, 96)):   # block-aligned pieces
        yi, (conv, hs) = ssm.mamba_forward(TINY, p, x[:, a:b], h0=hs,
                                           conv0=conv, return_state=True)
        ys.append(yi)
    np.testing.assert_allclose(np.concatenate(ys, 1), y, atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(hs, h, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(conv, tail, atol=1e-5, rtol=1e-5)


def test_trailing_padding_leaves_state_and_conv_tail_unchanged(params):
    p = _mamba_params(params)
    x = jax.random.normal(jax.random.key(3), (2, 64, TINY.d_model))
    lengths = jnp.asarray([37, 5], jnp.int32)
    pad = x.at[:, 40:].set(9.0)        # garbage past every valid length
    _, (tail, h) = ssm.mamba_forward(TINY, p, pad, return_state=True,
                                     lengths=lengths)
    for r, n in enumerate([37, 5]):
        _, (t_r, h_r) = ssm.mamba_forward(TINY, p, x[r:r + 1, :n],
                                          return_state=True)
        np.testing.assert_allclose(h[r], h_r[0], atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(tail[r], t_r[0], atol=1e-5, rtol=1e-5)
    # a row with no valid token passes its carried state through
    _, (t0, h0) = ssm.mamba_forward(TINY, p, pad, return_state=True,
                                    h0=h, conv0=tail,
                                    lengths=jnp.zeros((2,), jnp.int32))
    np.testing.assert_array_equal(h0, h)
    np.testing.assert_array_equal(t0, tail)


# ---------------------------------------------------------------------------
# Experts: the held share
# ---------------------------------------------------------------------------


def _moe_params(params):
    return jax.tree.map(lambda t: t[0], params["layers"]["slot0"]["moe"])


def test_dropless_share_equals_dense_oracle_on_held_experts(params):
    p = _moe_params(params)
    x = jax.random.normal(jax.random.key(4), (2, 24, TINY.d_model))
    valid = jnp.arange(24)[None, :] < jnp.asarray([[24], [10]])
    y, n, _ = moe.moe_share_forward(TINY, p, x, valid)
    want, _ = moe.moe_forward_dense(TINY, p, x)
    np.testing.assert_allclose(y[0], want[0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(y[1, :10], want[1, :10], atol=1e-5,
                               rtol=1e-5)
    # padding is routed nowhere: the shared expert alone
    shared = moe.apply_mlp(TINY, p["shared"], x[1, 10:])
    np.testing.assert_allclose(y[1, 10:], shared, atol=1e-5, rtol=1e-5)
    _, idx, _ = moe.router_topk(TINY, p, x.reshape(-1, TINY.d_model))
    held = (idx < TINY.experts_held) & valid.reshape(-1, 1)
    assert int(n) == int(held.sum()) > 0


def test_four_shares_add_up_to_the_uncut_layer():
    """Four chips holding two experts each: their results, with the
    shared expert (which each computes alike) counted once, add up to
    the layer that holds all eight."""
    whole = dataclasses.replace(TINY, n_experts_held=0)
    p = moe.moe_init(jax.random.key(5), whole)
    x = jax.random.normal(jax.random.key(6), (2, 16, TINY.d_model))
    want, _, _ = moe.moe_share_forward(whole, p, x)
    shared = moe.apply_mlp(TINY, p["shared"], x)
    total = shared
    for j in range(4):
        # chip j holds experts [2j, 2j + 2): renumbered to [0, 2), with
        # the router's columns in the same order
        pj = {"router": jnp.roll(p["router"], -2 * j, axis=1),
              "experts": jax.tree.map(lambda t: t[2 * j:2 * j + 2],
                                      p["experts"]),
              "shared": p["shared"]}
        yj, _, _ = moe.moe_share_forward(TINY, pj, x)
        total = total + (yj - shared)
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("positions, table", [("none", False),
                                              ("rope", False),
                                              ("learned", True)])
def test_only_learned_positions_hold_a_table(positions, table):
    c = dataclasses.replace(TINY, positions=positions)
    assert ("pos" in lm.init_abstract(c)["embed"]) is table
    assert get_config("granite-4.0-h-small").positions == "none"
    assert get_config("jamba-v0.1-52b").positions == "none"


# ---------------------------------------------------------------------------
# The chunked paged engine against the reference
# ---------------------------------------------------------------------------


class _Spans:
    """Stands in for ``TraceAnnotation``: records each span's kwargs."""

    seen: list = []

    def __init__(self, name, **kw):
        self.kw = dict(kw)
        _Spans.seen.append((name, self.kw))

    def set_metadata(self, **kw):
        self.kw.update(kw)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _serve(params, monkeypatch, reqs, **kw):
    import repro.serve.engine as E
    _Spans.seen = []
    monkeypatch.setattr(E, "TraceAnnotation", _Spans)
    eng = ServeEngine(TINY, params, cache="paged", sched="chunked",
                      block_size=16, chunk_tokens=32, decode_window=8, **kw)
    return eng, eng.serve(reqs)


def _widest_gap(params, res, prompts):
    ref = _ref()
    worst = 0.0
    for r in res:
        seq = np.concatenate([prompts[r.rid], np.asarray(r.tokens, np.int32)])
        lg = np.asarray(ref.sequence_logits(TINY_FILE, params,
                                            jnp.asarray(seq[None])))[0]
        lo, n = len(prompts[r.rid]) - 1, len(r.tokens)
        lg = lg[lo:lo + n]
        worst = max(worst, float((lg.max(-1)
                                  - lg[np.arange(n), r.tokens]).max()))
    return worst


@pytest.mark.parametrize("case", ["mixed", "preempted", "in_step"])
def test_chunked_engine_matches_the_reference(params, monkeypatch, case):
    """Prompts longer than a chunk resume their Mamba state chunk by
    chunk, each slot's slice in a call of its own: second chunks of 8
    and 13 tokens at one (bucket, depth); an oversubscribed pool
    preempts the younger request, which prefills again and replays its
    tail; four prompts chunk in step, so each iteration's four calls
    share one program and each gathers its own slot's state."""
    rng = np.random.default_rng(7)
    if case == "mixed":
        shapes, kw = [(40, 6), (45, 5), (100, 4), (9, 7)], {
            "n_slots": 4, "max_len": 128}
    elif case == "preempted":
        shapes, kw = [(40, 50), (36, 45)], {
            "n_slots": 2, "max_len": 96, "n_blocks": 9}
    else:
        shapes, kw = [(66, 5), (70, 6), (75, 4), (80, 7)], {
            "n_slots": 4, "max_len": 96}
    prompts = {i: rng.integers(0, TINY.vocab, p, np.int32)
               for i, (p, _n) in enumerate(shapes)}
    reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=n,
                    arrival_s=0.0) for i, (_p, n) in enumerate(shapes)]
    eng, out = _serve(params, monkeypatch, reqs, **kw)
    assert all(r.finish_reason == "length" for r in out.results)
    assert [len(r.tokens) for r in sorted(out.results, key=lambda r: r.rid)] \
        == [n for _p, n in shapes]
    assert _widest_gap(params, out.results, prompts) < GAP_TOL
    pre = [k for n, k in _Spans.seen if n == "serve.prefill"]
    assert any(k["carried"] for k in pre)
    assert all(k["rows"] == 1 and k["carried"] == int(k["npre"] > 0)
               for k in pre)
    calls = [(k["iter"], k["bucket"], k["npre"], k["used"]) for k in pre]
    if case == "mixed":
        assert eng.preemptions == 0
        assert {(16, 2, 8), (16, 2, 13)} <= {c[1:] for c in calls}
    elif case == "preempted":
        assert eng.preemptions >= 1
    else:
        assert eng.preemptions == 0
        assert calls == [(it, b, d, u) for it, (b, d, us) in enumerate(
            [(32, 0, [32] * 4), (32, 2, [32] * 4), (16, 4, [2, 6, 11, 16])])
            for u in us]


def test_routed_counts_reach_the_emit_spans(params, monkeypatch):
    """Every program's count is fetched with a fetch the engine makes,
    and lands on the ``serve.emit`` span that follows it; the buffer rows
    are known from the shapes."""
    rng = np.random.default_rng(8)
    reqs = [Request(rid=i, prompt=rng.integers(0, TINY.vocab, p, np.int32),
                    max_new_tokens=n, arrival_s=0.0)
            for i, (p, n) in enumerate([(40, 5), (20, 3)])]
    eng, _out = _serve(params, monkeypatch, reqs, n_slots=2, max_len=64)
    local = sum(k.get("moe_local", 0) for n, k in _Spans.seen
                if n == "serve.emit")
    rows = sum(k["moe_rows"] for n, k in _Spans.seen
               if n in ("serve.prefill", "serve.decode") and "moe_rows" in k)
    assert not eng._routed
    # at most 2 of a token's 3 choices land on the 2 held experts, in 4
    # expert layers; the real tokens are 60 of prompt and 6 fed back
    assert 0 < local <= 4 * 2 * (60 + 6) < rows
    prefill = [k for n, k in _Spans.seen if n == "serve.prefill"]
    assert all(k["moe_rows"] == k["rows"] * k["bucket"] * 2 * 4
               for k in prefill)
