"""Unified WorkloadSpec API: registry, tag filtering, runner, records, CLI."""
import json
import logging
import os
import subprocess
import sys

import pytest

import repro.bench.workloads  # noqa: F401 - populate the registry
from repro.bench import (
    ResultRecord, SCHEMA_VERSION, UnknownWorkloadError, WorkloadRunner,
    WorkloadSpec, get_workload, iter_workloads, register, save_records,
    unregister, workload_names,
)
from repro.bench.records import load_records
from repro.bench.spec import Space
from repro.core.results import atomic_write_text, save_results
from repro.core.runner import StragglerWatchdog
from repro.power.methods import SyntheticPower, select_power_methods

SEVEN = ["heatmap", "kernels", "llm_train", "pipeline_gpt", "resnet50",
         "roofline", "serve"]


# ---------------------------------------------------------------------------
# registry + tags
# ---------------------------------------------------------------------------


def test_all_seven_paper_workloads_registered():
    assert set(SEVEN) <= set(workload_names())


def test_unknown_workload_error_names_the_registry():
    with pytest.raises(UnknownWorkloadError) as ei:
        get_workload("nope")
    msg = str(ei.value)
    assert "nope" in msg and "llm_train" in msg


def test_duplicate_registration_rejected():
    spec = _toy_spec("dup_workload")
    register(spec)
    try:
        with pytest.raises(ValueError, match="already registered"):
            register(spec)
    finally:
        unregister("dup_workload")


def test_tag_filtering():
    assert [s.name for s in iter_workloads(tags=["serve"])] \
        == ["serve", "serve_slo"]
    assert [s.name for s in iter_workloads(tags=["vision"])] == ["resnet50"]
    smoke = {s.name for s in iter_workloads(tags=["smoke"])}
    assert set(SEVEN) <= smoke        # every paper workload has a smoke run
    # names validate even when combined with tags
    with pytest.raises(UnknownWorkloadError):
        iter_workloads(names=["serve", "bogus"], tags=["smoke"])


def test_smoke_space_is_narrower_and_points_override():
    spec = get_workload("llm_train")
    full = spec.space_for(False).expand()
    smoke = spec.space_for(True).expand()
    assert 0 < len(smoke) < len(full)
    only16 = spec.space_for(False, {"global_batch": 16}).expand()
    assert {pt["global_batch"] for pt in only16} == {16}
    with pytest.raises(KeyError, match="no axis"):
        spec.space_for(False, {"bogus_axis": 1})


def test_multi_device_workloads_declare_their_floor():
    # pipeline_gpt's spec-level placement maps its stages onto "pp"
    pg = get_workload("pipeline_gpt")
    assert pg.placement.dict() == {"pp": 4} and pg.n_devices == 4
    # heatmap sweeps a placement AXIS up to dp8; the CLI sizes the host
    # platform from the sweep, not a scalar floor
    hm = get_workload("heatmap")
    assert hm.n_devices == 1 and hm.max_devices() == 8
    assert hm.max_devices(smoke=True) == 2
    assert get_workload("llm_train").max_devices() == 4


# ---------------------------------------------------------------------------
# ResultRecord schema
# ---------------------------------------------------------------------------


def test_result_record_roundtrip():
    rec = ResultRecord(workload="w", point={"bs": 8}, metrics={"tps": 1.5},
                       power_source="synthetic", n_devices=2, attempts=2)
    back = ResultRecord.from_dict(json.loads(json.dumps(rec.to_dict())))
    assert back == rec
    flat = rec.flat()
    assert flat["schema_version"] == SCHEMA_VERSION
    assert flat["bs"] == 8 and flat["tps"] == 1.5
    assert flat["power_source"] == "synthetic" and flat["attempts"] == 2


def test_result_record_rejects_unknown_schema_version():
    d = ResultRecord(workload="w", point={}).to_dict()
    d["schema_version"] = SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema_version"):
        ResultRecord.from_dict(d)
    d["schema_version"] = 0
    with pytest.raises(ValueError, match="schema_version"):
        ResultRecord.from_dict(d)


def test_save_and_load_records(tmp_path):
    recs = [ResultRecord(workload="w", point={"bs": b},
                         metrics={"tps": 10.0 * b}) for b in (1, 2)]
    save_records(recs, tmp_path)
    doc = json.loads((tmp_path / "results.json").read_text())
    assert doc["schema_version"] == SCHEMA_VERSION
    assert load_records(tmp_path / "results.json") == recs
    csv = (tmp_path / "results.csv").read_text()
    assert csv.splitlines()[0].startswith("schema_version,workload")


# ---------------------------------------------------------------------------
# WorkloadRunner
# ---------------------------------------------------------------------------


def _toy_spec(name, build=None, **kw):
    def default_build(pt, ctx):
        return {"run": lambda: {"value": pt["x"] * 10,
                                "seconds": 0.001}}

    return WorkloadSpec(name=name, analog="toy", space=Space({"x": [1, 2]}),
                        build=build or default_build,
                        tags=frozenset({"smoke"}), **kw)


def test_workload_runner_end_to_end(tmp_path):
    spec = _toy_spec("toy")
    runner = WorkloadRunner(spec, out_dir=str(tmp_path),
                            power_methods=[SyntheticPower(base=100.0)],
                            power_source="synthetic")
    recs = runner.run(verbose=False)
    assert [r.metrics["value"] for r in recs] == [10, 20]
    assert all(r.ok and r.power_source == "synthetic" for r in recs)
    out = tmp_path / "toy"
    assert (out / "results.json").exists()
    assert (out / "results.csv").exists()
    assert (out / "manifest.json").exists()
    assert load_records(out / "results.json") == recs


def test_workload_runner_retries_are_counted_and_logged(tmp_path, caplog):
    attempts = []

    def flaky_build(pt, ctx):
        def step():
            attempts.append(1)
            if len(attempts) < 2:
                raise RuntimeError("transient glitch")
            return {"ok": 1}
        return {"run": step}

    spec = _toy_spec("toy_flaky", build=flaky_build)
    with caplog.at_level(logging.WARNING, logger="repro.bench"):
        recs = WorkloadRunner(spec, out_dir=str(tmp_path), power="none",
                              retries=3,
                              point_overrides={"x": 1}).run(verbose=False)
    assert recs[0].ok and recs[0].attempts == 2
    assert "transient glitch" in caplog.text   # retried failure is visible


def test_workload_runner_fails_fast_on_fatal_error(tmp_path):
    # a ValueError is a deterministic bug, not a transient: retrying it
    # would burn the retry budget reproducing the same crash
    calls = []

    def broken_build(pt, ctx):
        def run():
            calls.append(1)
            raise ValueError("boom")
        return {"run": run}

    spec = _toy_spec("toy_broken", build=broken_build)
    recs = WorkloadRunner(spec, out_dir=str(tmp_path), power="none",
                          retries=2).run(verbose=False)
    assert all(r.status == "error" and "boom" in r.error for r in recs)
    assert all(r.attempts == 1 for r in recs)
    assert len(calls) == len(recs)     # exactly one attempt per point


def test_workload_runner_records_error_after_exhausted_retries(tmp_path):
    def broken_build(pt, ctx):
        return {"run": lambda: (_ for _ in ()).throw(
            RuntimeError("boom transient"))}

    spec = _toy_spec("toy_broken2", build=broken_build)
    recs = WorkloadRunner(spec, out_dir=str(tmp_path), power="none",
                          retries=2).run(verbose=False)
    assert all(r.status == "error" and "boom" in r.error for r in recs)
    assert all(r.attempts == 2 for r in recs)


def test_power_autoselect_labels_source():
    methods, source = select_power_methods("auto")
    assert source in ("rapl", "tpu_model", "synthetic")
    assert methods and methods[0].name == source
    assert select_power_methods("none") == ([], "none")
    ms, src = select_power_methods("synthetic", n_devices=3)
    assert src == "synthetic" and len(ms[0].devices()) == 3
    with pytest.raises(KeyError):
        select_power_methods("flux_capacitor")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_list_shows_all_workloads(capsys):
    from repro.bench.cli import main
    assert main(["list"]) == 0
    out = capsys.readouterr().out
    for name in SEVEN:
        assert name in out


def test_cli_points_parsing():
    from repro.bench.cli import _parse_points
    assert _parse_points("global_batch=16,global_batch=32,arch=x") == {
        "global_batch": [16, 32], "arch": ["x"]}
    assert _parse_points("rate_hz=1.5") == {"rate_hz": [1.5]}
    assert _parse_points(None) is None


def test_cli_run_and_report_roofline(tmp_path, capsys):
    """Cheapest full CLI pass: run the analysis-only workload, then render
    its saved records with `report` (no model execution, synthetic power)."""
    from repro.bench.cli import main
    assert main(["run", "--suite", "roofline", "--power", "synthetic",
                 "--out", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "roofline" in out and "all benchmarks complete" in out
    recs = load_records(tmp_path / "roofline" / "results.json")
    assert {r.point["mesh"] for r in recs} == {"single", "multi"}
    assert all(r.ok and r.power_source == "synthetic" for r in recs)
    assert main(["report", "--out", str(tmp_path)]) == 0
    assert "roofline" in capsys.readouterr().out


@pytest.mark.slow
def test_cli_smoke_suite_end_to_end():
    """The CI gate: every smoke-tagged workload through one CLI call on
    synthetic power (multi-device workloads on in-process CPU devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.bench", "run", "--tags", "smoke",
         "--power", "synthetic", "--out", "artifacts/bench-smoke"],
        capture_output=True, text=True, timeout=1800, cwd=".", env=env)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "all benchmarks complete" in proc.stdout


# ---------------------------------------------------------------------------
# satellite fixes: watchdog warmup variance, atomic persistence
# ---------------------------------------------------------------------------


def test_straggler_watchdog_seeds_variance_from_warmup():
    w = StragglerWatchdog(k=3.0, warmup=3)
    for i, dt in enumerate([0.1, 0.2, 0.3]):
        assert not w.observe(i, dt)
    assert w.var > 0                       # warmup seeded the variance
    # ordinary spread after a noisy warmup must not flag (a zero-variance
    # baseline would have: 0.3 > 0.2 + 3 * 0.05 * 0.2)
    assert not w.observe(3, 0.3)
    assert w.observe(4, 5.0)               # a real straggler still flags


def test_save_results_survives_interrupted_write(tmp_path, monkeypatch):
    save_results([{"a": 1}], tmp_path, "results")
    before = (tmp_path / "results.json").read_text()

    def boom(src, dst):
        raise OSError("simulated crash mid-save")

    monkeypatch.setattr("repro.core.results.os.replace", boom)
    with pytest.raises(OSError):
        save_results([{"a": 1}, {"a": 2}], tmp_path, "results")
    monkeypatch.undo()
    assert (tmp_path / "results.json").read_text() == before
    leftovers = [p for p in tmp_path.iterdir() if p.name.startswith(".")]
    assert leftovers == []                 # tmp files cleaned up on failure


def test_atomic_write_text_replaces_content(tmp_path):
    p = tmp_path / "f.txt"
    atomic_write_text(p, "one")
    atomic_write_text(p, "two")
    assert p.read_text() == "two"
    assert [q.name for q in tmp_path.iterdir()] == ["f.txt"]
