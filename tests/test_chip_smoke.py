"""``chip_smoke.py``'s phases at a tiny size on the CPU.

The script itself refuses to run off a TPU; here each phase runs with
reduced configs and the Pallas kernels in interpret mode, so a wrong
path, argument or control flow shows up without a chip.
"""
import functools
import importlib.util
import json
import pathlib

import pytest

from repro.configs import get_config

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "get_config",
                        lambda name: get_config(name).reduced())
    for name, value in dict(TRAIN_SEQ=32, TRAIN_BATCH=2, TRAIN_STEPS=2,
                            SERVE_SLOTS=3, SERVE_MAX_LEN=96, SERVE_CHUNK=32,
                            SERVE_PROMPTS=(48, 64), SERVE_GEN=4,
                            FOUR_BATCH=8, FOUR_STEPS=2).items():
        monkeypatch.setattr(mod, name, value)
    return mod


@pytest.fixture
def interpret_kernels(monkeypatch):
    from repro.kernels import decode_attention, prefill_attention
    from repro.serve import engine
    for mod, name in ((decode_attention, "paged_decode_attention"),
                      (prefill_attention, "paged_prefill_attention")):
        monkeypatch.setattr(mod, name, functools.partial(getattr(mod, name),
                                                         interpret=True))
    monkeypatch.setattr(engine, "paged_kernel", lambda: ("pallas", True))


def test_device_phase_names_the_platform_it_found(smoke):
    with pytest.raises(smoke.PhaseFailed, match="'cpu'"):
        smoke.phase_device(1)
    assert smoke.main([]) == 1


def test_train_phase(smoke, capsys):
    smoke.phase_train(smoke.CompileClock())
    assert "[train] losses=" in capsys.readouterr().out


def test_kernel_phase(smoke, interpret_kernels, capsys):
    smoke.phase_kernels()
    assert capsys.readouterr().out.count("[kernels] pool=") == 2


def test_serve_phase(smoke, interpret_kernels, capsys):
    smoke.phase_serve(smoke.CompileClock())
    assert "requests=3" in capsys.readouterr().out


def test_serve_phase_refuses_the_xla_reference(smoke):
    with pytest.raises(smoke.PhaseFailed, match="'xla'"):
        smoke.phase_serve(smoke.CompileClock())


def test_four_chip_phase_on_four_cpu_devices(subproc):
    out = subproc(f"""
import importlib.util, json, sys
from repro.configs import get_config
spec = importlib.util.spec_from_file_location("s", {str(ROOT / "chip_smoke.py")!r})
s = importlib.util.module_from_spec(spec)
spec.loader.exec_module(s)
s.get_config = lambda name: get_config(name).reduced()
s.TRAIN_SEQ, s.FOUR_STEPS, s.FOUR_LAYERS = 32, 2, 2
s.phase_four_chips(s.CompileClock())
print(json.dumps({{"done": True}}))
""", n_devices=4)
    assert "[four] dp4" in out
    assert json.loads(out.strip().splitlines()[-1]) == {"done": True}
