"""A training cell's run at a CPU size, sound and with its timed path
broken underneath: ``correct`` has to come out true, then false for each
fault a training cell can have and for the fp8 control in the program's
place."""
import jax
import pytest
from bench_helpers import (cpu_run, shrink_train,  # noqa: F401 (fixture)
                           shrink_train_wide)

CELL = "train.gpt-800m.s2048"


def _broken_step(monkeypatch, breaker):
    import repro.train.step as st
    real = st.make_train_step

    def make(*a, **k):
        return breaker(real(*a, **k))
    monkeypatch.setattr(st, "make_train_step", make)


def test_sound_run_is_correct(cpu_run):
    line = cpu_run(CELL, shrink_train)
    assert line["correct"], line["compared"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"train_step_ms", "setup_s"}


def test_state_left_unchanged_is_not_correct(cpu_run, monkeypatch):
    def breaker(step):
        def same(p, o, b):
            _, _, m = step(p, o, b)
            return p, o, m
        return same
    _broken_step(monkeypatch, breaker)
    line = cpu_run(CELL, shrink_train)
    assert not line["correct"]
    assert line["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(cpu_run, monkeypatch):
    def breaker(step):
        def half(p, o, b):
            return step(p, o, jax.tree.map(lambda x: x[:x.shape[0] // 2], b))
        return half
    _broken_step(monkeypatch, breaker)
    line = cpu_run(CELL, lambda f, d: shrink_train(f, d, global_batch=4))
    assert not line["correct"], line["compared"]


def test_fp8_control_is_not_correct(cpu_run, monkeypatch):
    """The control in the program's place: the reference computed from fp8
    operands gives the readings the program's run would, judged by the
    run's own rule."""
    def prepare(files, driver):
        shrink_train_wide(files, driver)
        real_ref, real_compare = (driver.ref_train.reference_readings,
                                  driver.compare)
        seen = {}

        def reference(*a, **k):
            seen["args"] = a
            return real_ref(*a, **k)

        def compare(readings, ref, limits):
            return real_compare(real_ref(*seen["args"], quant="fp8"), ref,
                                limits)
        monkeypatch.setattr(driver.ref_train, "reference_readings", reference)
        monkeypatch.setattr(driver, "compare", compare)
    line = cpu_run(CELL, prepare)
    assert not line["correct"], line["compared"]
