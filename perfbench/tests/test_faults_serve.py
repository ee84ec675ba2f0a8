"""A serve cell's run at a CPU size, sound, with a served token altered
where the engine produces it, and with the fp8 control in the program's
place: ``correct`` has to come out true, then false."""
from bench_helpers import (cpu_run, shrink_serve,  # noqa: F401 (fixture)
                           shrink_serve_wide)

CELL = "serve.granite-8b-l9.chat"


def test_sound_run_is_correct(cpu_run):
    line = cpu_run(CELL, shrink_serve, seconds=1.0)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] == 8
    assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert list(line)[-1] == "compared"


def test_altered_token_is_not_correct(cpu_run, monkeypatch):
    from repro.serve.engine import ServeEngine
    real = ServeEngine._paged_step_fn

    def altered(self, nb):
        fn = real(self, nb)

        def step(*a):
            tok, caches = fn(*a)
            return (tok + 1) % self.c.vocab, caches
        return step
    monkeypatch.setattr(ServeEngine, "_paged_step_fn", altered)
    line = cpu_run(CELL, shrink_serve, seconds=1.0)
    assert not line["correct"]
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_control_is_not_correct(cpu_run, monkeypatch):
    """The control in the program's place: every served token replaced by
    the one the fp8 reference puts first, judged by the run's own rule."""
    def prepare(files, driver):
        shrink_serve_wide(files, driver)
        real = driver.served_gaps

        def as_control(cfg, params, results, max_len, quant=None):
            return real(cfg, params, results, max_len, quant=quant or "fp8")
        monkeypatch.setattr(driver, "served_gaps", as_control)
    line = cpu_run(CELL, prepare, seconds=1.0)
    assert not line["correct"], line["compared"]
