"""The harness: it refuses a run without a TPU, and every name in
``BENCHMARK.json`` finds its files."""
import json
import pathlib
import subprocess
import sys

import bench_helpers  # noqa: F401 (puts the program on the path)
import pytest

from perfbench import run as R

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_cpu_only_run_is_refused():
    p = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         "train.gpt-800m.s2048", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(ROOT)})
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


def test_device_check_wants_the_cell_s_chips(monkeypatch):
    with pytest.raises(R.NoChip):
        R.device_info(1)


BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_finds_its_files(cell):
    from perfbench.model import reference_of
    f = R.cell_files(BENCH, cell)
    path = R.HERE / "drivers" / f"{f['mix']['driver']}.py"
    assert path.exists()
    ref = reference_of(f["config"])
    for fn in R.load_module(path).REFERENCE_CALLS:
        assert callable(getattr(ref, fn, None)), (ref.__name__, fn)
    e2e, layers = R.cell_metrics(BENCH, cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and layers
    for m in layers:
        assert (R.HERE / "layers" / f"{m['name']}.py").exists()


def test_metric_names_and_arrows():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        for w in m["workloads"]:
            mv = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
            assert "workloads" not in mv or w in mv["workloads"]


def test_configs_state_what_runs():
    from perfbench.model import model_config
    for c in BENCH["configs"]:
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    for f in sorted((R.HERE / "configs").glob("*.json")):
        cfg = json.loads(f.read_text())
        assert f.stem == cfg["name"]
        assert model_config(cfg).param_count() == cfg["param_count"]


@pytest.mark.parametrize("checks, failed, want", [
    ([("gap", 0.1, 0.3)], 0, True),
    ([("gap", 0.1, 0.3), ("loss", 0.02, 0.01)], 0, False),
    ([("gap", 0.1, 0.3)], 1, False),
])
def test_control_verdict_is_the_run_s_rule(checks, failed, want):
    from perfbench import control
    v = control.verdict(checks, failed)
    assert v["correct"] is want is R.judge(checks, failed)
    assert v["compared"] == R.compared(checks)
