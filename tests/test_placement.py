"""Mesh-aware placement: Placement normalization, schema-v3 records,
scaling-metric derivation + compare gating, and deferred SLURM records."""
import json

import pytest
from _prop import given, settings, st

from repro.bench import (
    Placement, ResultRecord, SCHEMA_VERSION, WorkloadRunner, WorkloadSpec,
    compare_sets, placement_label, point_key, stamp_scaling_metrics,
    unregister, workload,
)
from repro.bench.records import load_records, write_result_doc
from repro.bench.spec import Space


# ---------------------------------------------------------------------------
# Placement normalization
# ---------------------------------------------------------------------------


def test_placement_spellings_normalize_to_one_value():
    want = Placement.of({"dp": 2, "tp": 2})
    assert Placement.of("dp2tp2") == want
    assert Placement.of("dp=2,tp=2") == want
    assert Placement.of("tp2 dp2") == want          # order-insensitive
    assert Placement.of(want) is want
    assert want.label == "dp2tp2" and want.n_devices == 4


def test_placement_scalar_upconverts_to_pure_dp():
    p = Placement.of(4)
    assert p.dict() == {"dp": 4} and p.label == "dp4"
    assert Placement.of(None).label == "dp1"


def test_placement_mesh_always_has_data_and_model_axes():
    # the table-driven sharding rules name "data"/"model" unconditionally
    p = Placement.of("dp2")
    assert p.mesh_axes == ("data", "model") and p.mesh_shape == (2, 1)
    p = Placement.of({"dp": 2, "tp": 2})
    assert p.mesh_axes == ("data", "model") and p.mesh_shape == (2, 2)
    p = Placement.of({"pp": 4})
    assert "stage" in p.mesh_axes and p.n_devices == 4
    p = Placement.of({"pod": 2, "dp": 4, "tp": 2})
    assert p.mesh_axes == ("pod", "data", "model")
    assert p.mesh_shape == (2, 4, 2)


def test_placement_rejects_garbage():
    for bad in ("nope", "2dp", "", "dp2dp4"):
        with pytest.raises(ValueError):
            Placement.of(bad)
    with pytest.raises(ValueError):
        Placement.of(0)
    with pytest.raises(ValueError):
        Placement.of({"dp": -1})
    with pytest.raises(TypeError):
        Placement.of(2.5)


# ---------------------------------------------------------------------------
# @workload signature: scalar back-compat, placement kwarg
# ---------------------------------------------------------------------------


def _register_toy(**kw):
    return workload("toy_placement", analog="t",
                    space=Space({"x": [1]}), **kw)(
        lambda pt, ctx: {"run": lambda: {"seconds": 0.0}})


def test_workload_scalar_n_devices_upconverts():
    spec = _register_toy(n_devices=8)
    try:
        assert spec.placement.dict() == {"dp": 8}
        assert spec.n_devices == 8
    finally:
        unregister("toy_placement")


def test_workload_placement_kwarg_and_conflict():
    spec = _register_toy(placement={"dp": 2, "tp": 2})
    try:
        assert spec.placement.label == "dp2tp2"
    finally:
        unregister("toy_placement")
    with pytest.raises(ValueError, match="not both"):
        _register_toy(placement="dp2", n_devices=2)


def test_placement_axis_drives_per_point_resolution():
    spec = WorkloadSpec(
        name="w", analog="t", build=lambda pt, ctx: {},
        space=Space({"placement": ["dp1", "dp2", "dp4"], "bs": [8]}))
    pts = spec.space_for().expand()
    assert [spec.placement_for(p).n_devices for p in pts
            if p["bs"] == 8] == [1, 2, 4]
    assert spec.max_devices() == 4
    # no placement axis -> the spec default answers for every point
    spec2 = WorkloadSpec(name="w2", analog="t", build=lambda pt, ctx: {},
                         space=Space({"bs": [8]}),
                         placement=Placement.of("pp4"))
    assert spec2.placement_for({"bs": 8}).label == "pp4"
    assert spec2.max_devices() == 4


# ---------------------------------------------------------------------------
# schema v3 records
# ---------------------------------------------------------------------------


def test_v2_record_upconverts_to_pure_dp(tmp_path):
    v2 = {"schema_version": 2, "workload": "w", "point": {"bs": 8},
          "metrics": {"tokens_per_s": 10.0}, "power_source": "synthetic",
          "n_devices": 4, "attempts": 1, "status": "ok", "error": None,
          "git_sha": "f" * 40, "noise": {"rel_std": 0.01}}
    path = tmp_path / "r.json"
    path.write_text(json.dumps(
        {"schema_version": 2, "workload": "w", "records": [v2]}))
    [rec] = load_records(path)
    assert rec.placement == {"dp": 4} and rec.n_devices == 4
    assert "plc=dp4" in point_key(rec)
    # and a v3 re-save of the same record joins the upconverted v2 one
    fresh = ResultRecord(workload="w", point={"bs": 8},
                         metrics={"tokens_per_s": 10.0},
                         power_source="synthetic", placement={"dp": 4})
    assert point_key(fresh) == point_key(rec)


def test_placement_label_matches_spec_canonicalization():
    # one canonicalization everywhere: record labels must equal
    # Placement.label even for meshes whose canonical order is not
    # alphabetical (pod sorts first by _AXIS_ORDER, not by name)
    pod = {"pod": 2, "dp": 4}
    assert placement_label(pod) == Placement.of(pod).label == "pod2dp4"
    assert placement_label({"tp": 2, "dp": 2}) == "dp2tp2"


def test_placement_field_reconciles_n_devices():
    r = ResultRecord(workload="w", point={}, placement={"tp": 2, "dp": 2})
    assert r.n_devices == 4
    assert r.flat()["placement"] == "dp2tp2"
    assert r.schema_version == SCHEMA_VERSION == 3
    back = ResultRecord.from_dict(json.loads(json.dumps(r.to_dict())))
    assert back == r


@settings(max_examples=25)
@given(dp=st.integers(1, 64), tp=st.integers(1, 16),
       bs=st.integers(1, 512))
def test_placement_point_key_order_insensitive_property(dp, tp, bs):
    """The join key must not care how the placement dict was ordered —
    nor how the Space ordered its axes."""
    fwd = ResultRecord(workload="w", point={"bs": bs, "mode": "x"},
                       placement={"dp": dp, "tp": tp})
    rev = ResultRecord(workload="w", point={"mode": "x", "bs": bs},
                       placement={"tp": tp, "dp": dp})
    assert point_key(fwd) == point_key(rev)
    assert placement_label(fwd.placement) == placement_label(rev.placement)
    back = ResultRecord.from_dict(json.loads(json.dumps(fwd.to_dict())))
    assert point_key(back) == point_key(fwd)


# ---------------------------------------------------------------------------
# scaling metrics + compare gating
# ---------------------------------------------------------------------------


def _sweep(dp4_tok_s=400.0, dp4_eff_wh=4.0):
    """One llm-style sweep: dp1/dp2/dp4 cells of the same point."""
    def cell(n, tok_s, tokens_per_wh):
        return ResultRecord(
            workload="w", point={"bs": 8, "placement": f"dp{n}"},
            metrics={"tokens_per_s": tok_s, "tokens_per_wh": tokens_per_wh},
            power_source="synthetic", placement={"dp": n})

    recs = [cell(1, 100.0, 2.0), cell(2, 190.0, 1.9),
            cell(4, dp4_tok_s, dp4_eff_wh)]
    stamp_scaling_metrics(recs)
    return recs


def test_stamp_scaling_metrics_against_the_dp1_cell():
    r1, r2, r4 = _sweep()
    assert r1.metrics["tok_s_per_device"] == 100.0
    assert "scaling_efficiency" not in r1.metrics   # 1-dev cell is the ref
    assert r2.metrics["tok_s_per_device"] == 95.0
    assert r2.metrics["scaling_efficiency"] == pytest.approx(0.95)
    # wh/token ratio vs dp1 = eff_1 / eff_n
    assert r2.metrics["wh_per_token_scaling"] == pytest.approx(2.0 / 1.9)
    assert r4.metrics["scaling_efficiency"] == pytest.approx(1.0)
    assert r4.metrics["wh_per_token_scaling"] == pytest.approx(0.5)


def test_stamp_scaling_metrics_emulation_device_cap():
    """device_cap=1 (a 1-core host faking N devices): per-device figures
    normalize by min(n, cap), effective_devices is recorded, and the
    wh ratio is rescaled by n_eff/n to cancel the synthetic power model
    billing each fake device as a full chip."""
    def cell(n, tok_s, tokens_per_wh):
        return ResultRecord(
            workload="w", point={"bs": 8, "placement": f"dp{n}"},
            metrics={"tokens_per_s": tok_s, "tokens_per_wh": tokens_per_wh},
            power_source="synthetic", placement={"dp": n})

    r1, r2 = cell(1, 100.0, 2.0), cell(2, 190.0, 1.9)
    stamp_scaling_metrics([r1, r2], device_cap=1)
    assert r1.metrics["tok_s_per_device"] == 100.0
    assert "effective_devices" not in r1.metrics      # n_eff == n == 1
    assert r2.metrics["effective_devices"] == 1
    assert r2.metrics["tok_s_per_device"] == 190.0    # / n_eff, not / 2
    assert r2.metrics["scaling_efficiency"] == pytest.approx(1.9)
    assert r2.metrics["wh_per_token_scaling"] == pytest.approx(
        (2.0 / 1.9) * 0.5)
    # a cap at/above the mesh is a no-op — real-hardware semantics
    r1b, r2b = cell(1, 100.0, 2.0), cell(2, 190.0, 1.9)
    stamp_scaling_metrics([r1b, r2b], device_cap=8)
    assert r2b.metrics["tok_s_per_device"] == 95.0
    assert "effective_devices" not in r2b.metrics
    assert r2b.metrics["scaling_efficiency"] == pytest.approx(0.95)


def test_scaling_floor_violations_flags_collapsed_cells():
    from repro.bench import scaling_floor_violations
    recs = _sweep(dp4_tok_s=120.0)                    # dp4 eff 0.3
    viol = scaling_floor_violations(recs, floor=0.6)
    assert [(r.point["placement"], round(e, 2)) for r, e in viol] == [
        ("dp4", 0.3)]
    assert scaling_floor_violations(_sweep(), floor=0.6) == []


def test_stamp_scaling_metrics_without_dp1_twin_stays_silent():
    lone = ResultRecord(workload="w", point={"bs": 8, "placement": "dp4"},
                        metrics={"tokens_per_s": 400.0},
                        placement={"dp": 4})
    stamp_scaling_metrics([lone])
    assert lone.metrics["tok_s_per_device"] == 100.0
    assert "scaling_efficiency" not in lone.metrics


def test_compare_classifies_degraded_dp4_cell_as_regressed():
    """The acceptance drill: a dp4 cell whose scaling collapsed gates
    the compare engine even though its raw dp1 twin is untouched."""
    baseline = _sweep()                              # healthy: eff 1.0
    degraded = _sweep(dp4_tok_s=120.0, dp4_eff_wh=0.8)  # eff 0.3, wh 2.5x
    cmp = compare_sets(baseline, degraded)
    by_plc = {p.point["placement"]: p for p in cmp.points}
    assert by_plc["dp1"].status == "unchanged"
    assert by_plc["dp4"].status == "regressed"
    bad = {d.metric for d in by_plc["dp4"].deltas
           if d.status == "regressed"}
    assert "scaling_efficiency" in bad and "wh_per_token_scaling" in bad
    assert cmp.exit_code(fail_on_regression=True) == 3


# ---------------------------------------------------------------------------
# deferred records (mesh exceeds local devices)
# ---------------------------------------------------------------------------


def _toy_sweep_spec():
    def build(pt, ctx):
        assert ctx.placement.n_devices == 1     # dp64 never builds
        return {"run": lambda: {"tokens_per_s": 100.0, "seconds": 0.001}}

    return WorkloadSpec(
        name="toy_defer", analog="t", build=build,
        space=Space({"placement": ["dp1", "dp64"], "bs": [8]}))


def test_oversized_mesh_defers_with_rendered_slurm_script(tmp_path):
    runner = WorkloadRunner(_toy_sweep_spec(), out_dir=str(tmp_path),
                            power="none")
    recs = runner.run(verbose=False)
    by = {r.point["placement"]: r for r in recs}
    assert by["dp1"].ok
    deferred = by["dp64"]
    assert deferred.status == "deferred" and not deferred.ok
    assert deferred.n_devices == 64
    script = deferred.metrics["slurm_script"]
    # one script PER POINT: non-placement axes are in the filename so
    # same-mesh cells of a sweep cannot clobber each other's script
    text = (tmp_path / "toy_defer").joinpath(
        "slurm", "toy_defer_dp64_bs8.sbatch").read_text()
    assert script.endswith("toy_defer_dp64_bs8.sbatch")
    assert "#SBATCH --nodes=16" in text            # 64 chips / 4 per host
    assert "--suite toy_defer" in text and "placement=dp64" in text
    # the invoking run's settings ride along so the cluster record joins
    # the local result set (out tree + power label are in the point key)
    assert f"--out {tmp_path}" in text and "--power none" in text
    # round-trips through the schema and loads back as deferred
    loaded = load_records(tmp_path / "toy_defer" / "results.json")
    assert {r.status for r in loaded} == {"ok", "deferred"}


def test_oversized_mesh_on_an_accelerator_is_an_error(tmp_path,
                                                      monkeypatch):
    """On a chip host the chips present are the host under test: a point
    wider than them fails (and so fails ``run``), it is never deferred."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    runner = WorkloadRunner(_toy_sweep_spec(), out_dir=str(tmp_path),
                            power="none")
    by = {r.point["placement"]: r for r in runner.run(verbose=False)}
    assert by["dp1"].ok
    assert by["dp64"].status == "error"
    assert "needs 64 devices" in by["dp64"].error
    assert "slurm_script" not in by["dp64"].metrics


def test_compare_treats_deferred_as_missing_not_regression(tmp_path):
    ok = ResultRecord(workload="w", point={"placement": "dp64"},
                      metrics={"tokens_per_s": 1.0}, placement={"dp": 64})
    deferred = ResultRecord(workload="w", point={"placement": "dp64"},
                            placement={"dp": 64}, status="deferred",
                            error="mesh dp64 needs 64 devices")
    cmp = compare_sets([ok], [deferred])
    [p] = cmp.points
    assert p.status == "missing" and "deferred" in p.note
    assert cmp.exit_code(fail_on_regression=True) == 0
    assert cmp.exit_code(fail_on_missing=True) == 4
    # a deferred record must never be promoted as a baseline
    from repro.bench import promote
    assert promote([deferred], tmp_path) == []
