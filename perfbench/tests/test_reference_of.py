"""Each configuration names its plain reference (``"reference"``, default
``model``), and the drivers take the weights and the comparison from it:
naming ``model`` reads what naming nothing reads, a planted reference
makes a sound run read ``correct: false``, and an unknown name stops the
run before set-up."""
import pathlib

import pytest
from bench_helpers import (cpu_run, shrink_serve,  # noqa: F401 (fixture)
                           shrink_serve_wide, shrink_train)

from perfbench import model as M

PLANTED = pathlib.Path(__file__).resolve().parent / "planted"
CELLS = {"train": ("train.gpt-800m.s2048", shrink_train),
         "serve": ("serve.granite-8b-l9.chat", shrink_serve)}


def naming(reference, shrink):
    def prepare(files, driver):
        shrink(files, driver)
        files["config"]["reference"] = reference
    return prepare


def test_default_reference_is_model():
    cfg = {"name": "x"}
    ref = M.reference_of(cfg)
    assert pathlib.Path(ref.__file__).resolve() == M.REFERENCES / "model.py"
    assert M.reference_of(dict(cfg, reference="model")) is ref


def test_train_naming_model_reads_as_naming_nothing(cpu_run):
    cell, shrink = CELLS["train"]
    plain = cpu_run(cell, shrink)
    named = cpu_run(cell, naming("model", shrink))
    assert plain["correct"] and named["correct"]
    assert named["compared"] == plain["compared"]


def test_serve_naming_model_reads_as_naming_nothing(cpu_run, monkeypatch):
    """One run's served tokens, judged under both names: the same gaps."""
    cell, shrink = CELLS["serve"]
    seen = []

    def prepare(files, driver):
        shrink(files, driver)
        real = driver.served_gaps

        def both(cfg, params, results, max_len, quant=None):
            gaps = real(cfg, params, results, max_len, quant)
            named = real(dict(cfg, reference="model"), params, results,
                         max_len, quant)
            seen.append(([g.tolist() for g in gaps],
                         [g.tolist() for g in named]))
            return gaps
        monkeypatch.setattr(driver, "served_gaps", both)
    line = cpu_run(cell, prepare, seconds=1.0)
    assert line["correct"], line["compared"]
    (gaps, named), = seen
    assert gaps and named == gaps


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_planted_reference_is_not_correct(cpu_run, monkeypatch, kind):
    """At the tiny serve size the planted gap reads about the limit (the
    logits span less than 1): the serve cell runs at half its width."""
    cell, shrink = CELLS[kind]
    if kind == "serve":
        shrink = shrink_serve_wide
    monkeypatch.setattr(M, "REFERENCES", PLANTED)
    line = cpu_run(cell, naming("no_last_mlp", shrink), seconds=1.0)
    assert not line["correct"], line["compared"]
    assert line["failed"] == 0


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_unknown_reference_stops_before_set_up(cpu_run, kind):
    cell, shrink = CELLS[kind]

    def prepare(files, driver):
        naming("no_such_reference", shrink)(files, driver)

        def set_up(cfg):
            raise AssertionError("set-up began")
        driver.model_config = set_up
    with pytest.raises(FileNotFoundError) as e:
        cpu_run(cell, prepare)
    assert str(M.REFERENCES / "no_such_reference.py") in str(e.value)
