"""The program's spans in a profile: the serve engine's program calls
rebuilt from their kwargs, the readers of its counters, and idle gaps put
down to the innermost benchmark or program span."""
import pathlib

import bench_helpers  # noqa: F401 (puts the program on the path)
import numpy as np
import pytest

from perfbench import run as R
from perfbench import spans
from perfbench import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"
SERVE_SPANS = {"serve.iter", "serve.admit", "serve.prefill",
               "serve.prefill.fetch", "serve.decode", "serve.decode.fetch",
               "serve.emit", "serve.wait", "serve.first_call"}


def reader(name):
    return R.load_module(R.HERE / "layers" / f"{name}.py").read


@pytest.fixture(scope="module")
def traced_serve(tmp_path_factory):
    """One chunked ``serve`` of a tiny engine under the profiler: three
    requests at once and a late one, on a fake clock."""
    import jax

    from repro.configs import get_config
    from repro.models import lm
    from repro.serve.engine import ServeEngine
    from repro.serve.requests import Request
    c = get_config("llama3.2-3b").reduced(dtype="float32",
                                          param_dtype="float32")
    t = [0.0]
    eng = ServeEngine(c, lm.init(jax.random.key(0), c), cache="paged",
                      n_slots=3, max_len=96, block_size=16, chunk_tokens=32,
                      sched="chunked", clock=lambda: t[0],
                      sleep_fn=lambda s: t.__setitem__(0, t[0] + s))
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, c.vocab, p, np.int32),
                    max_new_tokens=n, arrival_s=a)
            for i, (p, n, a) in enumerate([(20, 6, 0.0), (40, 4, 0.0),
                                           (64, 3, 0.0), (24, 2, 1.0)])]
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    try:
        out = eng.serve(reqs)
    finally:
        jax.profiler.stop_trace()
    path = str(next(d.glob("**/*.xplane.pb")))
    return out, spans.host_events(path), d


def test_trace_holds_every_serve_span(traced_serve):
    _out, events, _d = traced_serve
    assert SERVE_SPANS <= {n for n, *_ in events}


def test_span_step_kwarg_indexes_its_record(traced_serve):
    out, events, _d = traced_serve
    recs = spans.records(events)
    assert [r["step"] for r in recs] == list(range(len(out.steps)))
    for r in recs:
        s = out.steps[r["step"]]
        assert (r["kind"], r["iter"], r["rows"], r["width"], r["used"],
                r["n_steps"]) == (s.kind, s.iter, s.rows, s.width, s.used,
                                  s.n_steps)
    calls = [(n, kw) for n, _s, _e, kw in events if n == "serve.first_call"]
    assert sorted(tuple(sorted(kw.items())) for _n, kw in calls) == sorted(
        [(("bucket", 32), ("npre", 0)), (("bucket", 16), ("npre", 2)),
         (("bucket", 32), ("npre", 2)), (("nb", 2),), (("nb", 6),),
         (("nb", 4),)])


def test_readers_count_what_ran(traced_serve, monkeypatch):
    _out, _events, d = traced_serve
    monkeypatch.setattr(R, "TRACE_DIR", d)
    # prefill calls (see tests/test_serve_spans.py): 84 + 8 + 32 + 24
    # real tokens in 3 x (32 + 16 + 32 + 32) rows x tokens; in iters
    # 0, 1, 1, 24
    assert reader("prefill.row_fill")({}, {}, {}) == \
        pytest.approx(100 * 148 / 336)
    assert reader("prefill.calls_per_iter")({}, {}, {}) == 4 / 3
    # decode calls: 2 + 20 + 5 + 2 + 2 live blocks over
    # 3 x (2 + 6 x 2 + 4 + 2 + 2) gathered
    assert reader("decode.gather_fill")({}, {}, {}) == \
        pytest.approx(100 * 31 / 66)


def test_readers_find_nothing_without_the_program_s_spans(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(R, "TRACE_DIR", tmp_path)
    for name in ("prefill.row_fill", "prefill.calls_per_iter",
                 "decode.gather_fill"):
        assert reader(name)({}, {}, {}) is None


# ---------------------------------------------------------------------------
# Idle gaps put down to program spans
# ---------------------------------------------------------------------------


def plane(name, lines):
    return {"name": name, "lines": [{"name": n, "events": e}
                                    for n, e in lines.items()]}


# ops at [0,10) [30,40) [60,70) [3000070,3000080) ns on chip 0
DEVICE = plane("/device:TPU:0", {
    "XLA Ops": [("%fusion.1 = bf16[8]{0} fusion(%p)", 0, 10),
                ("%fusion.2 = bf16[8]{0} fusion(%p)", 30, 10),
                ("%step.4 = bf16[2]{0} custom-call(%q, %k)", 60, 10),
                ("%fusion.3 = bf16[8]{0} fusion(%p)", 3000070, 10)],
    "XLA Modules": [("jit_step(12)", 0, 3000080)]})
BENCH = [("bench.window", 0, 3000100), ("bench.step", 8, 25)]
PROGRAM = [("serve.iter", 5, 3000090), ("serve.decode.fetch", 38, 25),
           ("train", 3000000, 100)]


def _planes(host):
    return [DEVICE, plane("/host:CPU", {"python": host})]


def test_gaps_under_a_program_span_go_to_it():
    got = spans.idle(_planes(BENCH + PROGRAM), 1)
    # gaps [10,30) under bench.step (innermost: 25 ns inside the 3 ms
    # serve.iter), [40,60) under serve.decode.fetch, [70,3000070) under
    # serve.iter, [3000080,3000100) under the step span ``train``
    assert got == pytest.approx({
        "bench.step": 20e-9, "serve.decode.fetch": 20e-9,
        "serve.iter": 3e-3, "train": 20e-9})
    assert next(iter(got)) == "serve.iter"


def test_without_program_spans_the_gaps_go_where_reduce_puts_them():
    planes = _planes(BENCH + [("other", 40, 20)])
    got = spans.idle(planes, 1)
    assert got == dict(tr.reduce(planes, 1)["top_gaps"])
    assert set(got) == {"bench.step", "bench.window"}


def test_recorded_tpu_trace_reduces_as_before():
    planes = tr.load(str(DATA))
    assert spans.idle(planes, 1) == dict(tr.reduce(planes, 1)["top_gaps"])
    assert spans.records(spans.host_events(str(DATA))) == []
