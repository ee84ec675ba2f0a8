"""The hybrid serve cell's run at a CPU size: sound, it reads ``correct``
true; with the Mamba state that a chunk carries into the next zeroed at
every chunk boundary (each chunk after the first starts its recurrence
afresh), and with the fp8 control in the program's place, it has to read
false by the cell's own limit."""
import pytest
from bench_helpers import cpu_run  # noqa: F401 (fixture)
from hsmall_helpers import CELL, shrink, shrink_wide

#: prompts of 33 to 35 tokens: a 32-token chunk, then one of 1 to 3
#: tokens whose first served token reads the conv and SSM state carried
#: across the boundary. Where the last chunk is long the recurrence has
#: mostly forgotten a zeroed state by the time a token is served (the
#: random model's state decays within tens of tokens), and the fault
#: reads about as the sound run does.
EDGE = {"median": 34, "sigma": 0.05, "min": 33, "max": 35, "round_up": 1}


@pytest.mark.parametrize("prompt", [None, EDGE], ids=["chat", "edge"])
def test_sound_run_is_correct(cpu_run, prompt):
    line = cpu_run(CELL, lambda f, d: shrink(f, d, prompt=prompt),
                   seconds=1.0)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] == 8
    assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}


def zero_the_carried_state(monkeypatch):
    """Every chunk after the first starts its recurrence from zeros."""
    from repro.serve.engine import ServeEngine
    real = ServeEngine._prefix_prefill_fn

    def zeroed(self, bucket, npre):
        fn = real(self, bucket, npre)

        def call(params, caches, tokens, last, pre_blocks, slot_ids):
            # ids past the pool gather zeros: no state carried in
            return fn(params, caches, tokens, last, pre_blocks,
                      slot_ids * 0 + self.n_slots)
        return call
    monkeypatch.setattr(ServeEngine, "_prefix_prefill_fn", zeroed)


def test_sound_run_at_wide_widths_is_correct(cpu_run):
    line = cpu_run(CELL, shrink_wide, seconds=1.0)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0


def test_zeroed_carried_state_is_not_correct(cpu_run, monkeypatch):
    zero_the_carried_state(monkeypatch)

    def prepare(files, driver):
        shrink(files, driver, prompt=EDGE)
    line = cpu_run(CELL, prepare, seconds=1.0)
    assert not line["correct"], line["compared"]
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_zeroed_carried_state_at_chat_lengths_is_not_correct(cpu_run,
                                                            monkeypatch):
    """The same fault where the prompts' last chunks are whole, at the
    configuration's head widths: the served tokens still read the state
    carried from the chunks before, because the benchmark's weights keep
    long-memory heads (``A_RANGE``)."""
    zero_the_carried_state(monkeypatch)
    line = cpu_run(CELL, shrink_wide, seconds=1.0)
    assert not line["correct"], line["compared"]
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_control_is_not_correct(cpu_run, monkeypatch):
    """The control in the program's place: every served token replaced by
    the one the fp8 reference puts first, judged by the run's own rule."""
    def prepare(files, driver):
        shrink_wide(files, driver)
        real = driver.served_gaps

        def as_control(cfg, params, results, max_len, quant=None):
            return real(cfg, params, results, max_len, quant=quant or "fp8")
        monkeypatch.setattr(driver, "served_gaps", as_control)
    line = cpu_run(CELL, prepare, seconds=1.0)
    assert not line["correct"], line["compared"]
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
