"""The reduction from a profiler trace to busy time, time by operation,
exposed collectives and idle gaps put down to host spans."""
import pathlib

import pytest

from perfbench import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def plane(name, lines):
    return {"name": name, "lines": [{"name": n, "events": e}
                                    for n, e in lines.items()]}


# ops at [0,10) [5,20) [30,40) [60,70) ns on chip 0, [0,20) [10,15) on
# chip 1, named as the TPU profiler names them; host spans
PLANES = [
    plane("/device:TPU:0", {
        "XLA Ops": [("%while.5 = (s32[]) while(%t), body=%b", 0, 20),
                    ("%fusion.1 = bf16[8]{0} fusion(%p)", 0, 10),
                    ("%all-reduce.3 = f32[8]{0} all-reduce(%g)", 5, 15),
                    ("%fusion.2 = bf16[8]{0} fusion(%p)", 30, 10),
                    ("%step.4 = bf16[2]{0} custom-call(%q, %k)", 60, 10)],
        "XLA Modules": [("jit_train_step(11)", 0, 40),
                        ("jit_step(12)", 60, 10)]}),
    plane("/device:TPU:1", {
        "XLA Ops": [("%all-reduce.3 = f32[8]{0} all-reduce(%g)", 0, 20),
                    ("%fusion.1 = bf16[8]{0} fusion(%p)", 10, 5)]}),
    plane("/host:CPU", {"python": [
        ("bench.window", 0, 80), ("bench.step", 18, 14),
        ("other", 40, 20)]}),
]


def test_union_merges_overlaps():
    assert tr.union([(5, 20), (0, 10), (30, 40), (40, 41)]) == \
        [[0, 20], [30, 41]]


def test_busy_time_and_time_by_name():
    red = tr.reduce(PLANES, chips=1)
    assert red["busy_s"] == pytest.approx(40e-9)      # [0,20) [30,40) [60,70)
    assert red["ops"] == pytest.approx({
        "jit_train_step:fusion": 20e-9, "jit_train_step:all-reduce": 15e-9,
        "jit_step:custom-call": 10e-9})
    assert red["modules"] == pytest.approx({"jit_train_step": 40e-9,
                                            "jit_step": 10e-9})
    assert red["top_ops"][0] == ["jit_train_step:fusion",
                                 pytest.approx(20e-9)]


def test_busy_time_is_averaged_over_the_chips_used():
    red = tr.reduce(PLANES, chips=2)
    assert red["busy_s"] == pytest.approx((40e-9 + 20e-9) / 2)


def test_idle_gaps_go_to_the_innermost_span():
    red = tr.reduce(PLANES, chips=1)
    # window [0,80): gaps [20,30) under bench.step (innermost), [40,60)
    # and [70,80) under bench.window only
    gaps = dict(red["top_gaps"])
    assert gaps["bench.step"] == pytest.approx(10e-9)
    assert gaps["bench.window"] == pytest.approx(30e-9)
    assert red["n_gaps"] == 3


def test_exposed_collective_time():
    red = tr.reduce(PLANES, chips=2)
    # chip 0: all-reduce [5,20) under fusion [0,10): exposed [10,20)
    # chip 1: all-reduce [0,20) under fusion [10,15): exposed 15
    assert tr.exposed(red, ":all-reduce") == pytest.approx((10e-9 + 15e-9) / 2)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([PLANES[2]], chips=1)


def test_recorded_tpu_trace():
    """A v5e trace of a matmul and the two paged kernels, three times each
    under host spans (``data/record_trace.py``)."""
    red = tr.reduce(tr.load(str(DATA)), chips=1)
    assert 0 < red["busy_s"] < 0.01
    assert red["ops"]["jit_paged_decode_attention:custom-call"] > 0
    assert red["ops"]["jit__lambda:custom-call"] > 0
    assert red["modules"]["jit_paged_decode_attention"] > 0
    gaps = dict(red["top_gaps"])
    assert gaps["bench.host_wait"] > 1e-3
    assert sum(gaps.values()) < 0.03
