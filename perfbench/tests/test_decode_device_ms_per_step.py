"""The ``decode.device_ms_per_step`` reader on hand-made facts: the decode
program's device seconds over the decode micro-steps, and nothing (never
0) where either is absent."""
import pytest

from perfbench import run as R
from perfbench import spans


def reader():
    return R.load_module(
        R.HERE / "layers" / "decode.device_ms_per_step.py").read


def facts(modules):
    return {"trace": {"ops": {}, "modules": modules, "busy_s": 0.5,
                      "device_ops": [[]]}}


def _decode_call(k):
    return {"kind": "decode", "step": 0, "iter": 0, "rows": 16, "width": 64,
            "used": 100, "n_steps": k}


@pytest.mark.parametrize("modules,calls,want", [
    # 0.9 s of the decode program over 4 + 8 + 6 micro-steps
    ({"jit_step": 0.9, "jit_prefill_hit": 2.0},
     [_decode_call(4), _decode_call(8), _decode_call(6),
      {"kind": "prefill", "n_steps": 1}], 50.0),
    ({"jit_step": 0.9}, [{"kind": "prefill", "n_steps": 1}], None),
    ({"jit_step": 0.9}, [], None),
    ({"jit_prefill_hit": 2.0, "jit_step_other": 1.0}, [_decode_call(4)],
     None),
], ids=["steps", "no_decode", "no_calls", "no_jit_step"])
def test_decode_device_ms_per_step(monkeypatch, modules, calls, want):
    monkeypatch.setattr(spans, "engine_calls", lambda: calls)
    got = reader()(facts(modules), None, None)
    assert got == (None if want is None else pytest.approx(want))
