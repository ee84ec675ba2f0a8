"""Cross-run comparison engine: point-key join, noise-aware tolerances,
baseline promote/compare round trips, CLI regression gating, and the
power autoselection chain that labels the records compare joins on."""
import json

import pytest
from _prop import given, settings, st

from repro.bench import (
    ResultRecord, SCHEMA_VERSION, WorkloadRunner, WorkloadSpec,
    compare_sets, load_result_set, point_key, promote, save_records,
)
from repro.bench.cli import main
from repro.bench.compare import (
    IMPROVED, MISSING, NEW, POWER_MISMATCH, REGRESSED, UNCHANGED,
    diff_metric, effective_tolerance,
)
from repro.bench.records import load_records, write_result_doc
from repro.bench.spec import Space
from repro.core.runner import StragglerWatchdog
from repro.power.methods import RaplPower, select_power_methods


def rec(workload="w", point=None, metrics=None, power="synthetic",
        rel_std=0.0, **kw):
    return ResultRecord(
        workload=workload, point=point or {"bs": 8},
        metrics=metrics if metrics is not None else {"tokens_per_s": 100.0},
        power_source=power, noise={"rel_std": rel_std}, **kw)


# ---------------------------------------------------------------------------
# point key
# ---------------------------------------------------------------------------


def test_point_key_components():
    r = rec(point={"seq": 64, "global_batch": 8}, n_devices=4)
    key = point_key(r)
    assert key == "w|global_batch=8,seq=64|plc=dp4|power=synthetic"
    assert point_key(r, with_power=False) == "w|global_batch=8,seq=64|plc=dp4"


def test_point_key_distinguishes_power_and_devices():
    base = rec()
    assert point_key(base) != point_key(rec(power="rapl"))
    assert point_key(base) != point_key(rec(n_devices=2))
    assert point_key(base) != point_key(rec(point={"bs": 16}))
    # same device count, different mesh shape -> different measurement
    assert point_key(rec(placement={"dp": 4})) != \
        point_key(rec(placement={"dp": 2, "tp": 2}))


@settings(max_examples=25)
@given(a=st.integers(1, 512), b=st.integers(1, 512),
       c=st.floats(0.1, 100.0))
def test_point_key_order_insensitive_property(a, b, c):
    """The join key the whole engine depends on must not care how the
    Space happened to order its axes."""
    fwd = rec(point={"x": a, "y": b, "rate": c})
    rev = rec(point={"rate": c, "y": b, "x": a})
    assert point_key(fwd) == point_key(rev)
    assert point_key(fwd, with_power=False) == point_key(rev,
                                                         with_power=False)


@settings(max_examples=25)
@given(bs=st.integers(1, 1024), tps=st.floats(0.001, 1e6),
       wh=st.floats(0.0, 10.0), attempts=st.integers(1, 5),
       status=st.sampled_from(["ok", "error", "skipped"]),
       power=st.sampled_from(["rapl", "tpu_model", "synthetic", "none"]))
def test_result_record_json_roundtrip_property(bs, tps, wh, attempts,
                                               status, power):
    r = ResultRecord(workload="w", point={"bs": bs, "mode": "train"},
                     metrics={"tokens_per_s": tps, "wh_per_token": wh},
                     power_source=power, attempts=attempts, status=status,
                     error="boom" if status == "error" else None,
                     git_sha="f" * 40, noise={"rel_std": 0.01})
    back = ResultRecord.from_dict(json.loads(json.dumps(r.to_dict())))
    assert back == r
    assert point_key(back) == point_key(r)


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


def test_classification_regressed_improved_unchanged_missing_new():
    base = [rec(point={"bs": 1}, metrics={"tokens_per_s": 100.0}),
            rec(point={"bs": 2}, metrics={"tokens_per_s": 100.0}),
            rec(point={"bs": 3}, metrics={"tokens_per_s": 100.0}),
            rec(point={"bs": 4}, metrics={"tokens_per_s": 100.0})]
    cur = [rec(point={"bs": 1}, metrics={"tokens_per_s": 50.0}),   # -50%
           rec(point={"bs": 2}, metrics={"tokens_per_s": 200.0}),  # +100%
           rec(point={"bs": 3}, metrics={"tokens_per_s": 101.0}),  # noise
           # bs=4 vanished                                         -> missing
           rec(point={"bs": 5}, metrics={"tokens_per_s": 1.0})]    # new
    cmp = compare_sets(base, cur)
    by = {p.point["bs"]: p.status for p in cmp.points}
    assert by == {1: REGRESSED, 2: IMPROVED, 3: UNCHANGED,
                  4: MISSING, 5: NEW}
    assert cmp.exit_code() == 0
    assert cmp.exit_code(fail_on_regression=True) != 0
    assert [p.point["bs"] for p in cmp.regressions] == [1]


def test_lower_is_better_metrics_direction():
    base = [rec(metrics={"seconds": 1.0, "wh_per_token": 1.0})]
    slower = [rec(metrics={"seconds": 2.0, "wh_per_token": 0.2})]
    cmp = compare_sets(base, slower)
    (p,) = cmp.points
    assert p.status == REGRESSED          # time regressed wins over energy
    by_metric = {d.metric: d.status for d in p.deltas}
    assert by_metric == {"seconds": REGRESSED, "wh_per_token": IMPROVED}


def test_current_error_at_ok_baseline_point_is_a_regression():
    base = [rec()]
    cur = [rec(metrics={}, status="error", error="OOM")]
    cmp = compare_sets(base, cur)
    assert cmp.points[0].status == REGRESSED
    assert "OOM" in cmp.points[0].note
    # an errored *baseline* record gates nothing
    cmp2 = compare_sets(cur, base)
    assert cmp2.points[0].status == NEW


def test_skipped_current_point_is_missing_not_errored():
    base = [rec()]
    cur = [rec(metrics={}, status="skipped")]
    (p,) = compare_sets(base, cur).points
    assert p.status == MISSING and "skipped" in p.note
    assert compare_sets(base, cur).exit_code(fail_on_regression=True) == 0
    assert compare_sets(base, cur).exit_code(fail_on_missing=True) != 0


def test_additional_power_source_is_reported_not_dropped():
    """When the current run carries both the baseline's power source and
    an extra one, the extra measurement must surface as `new` — not
    vanish from the report."""
    base = [rec(power="synthetic")]
    cur = [rec(power="synthetic"),
           rec(power="rapl", metrics={"tokens_per_s": 90.0})]
    cmp = compare_sets(base, cur)
    by = {p.power_source: p.status for p in cmp.points}
    assert by == {"synthetic": UNCHANGED, "rapl": NEW}


def test_dual_power_baseline_with_clean_match_is_missing_not_mismatch():
    """A baseline measured under two power sources, re-run under one:
    the matched pair compares cleanly, so the other baseline row is
    merely absent — it must not fail --fail-on-regression as a
    power_mismatch."""
    base = [rec(power="synthetic"), rec(power="rapl")]
    cur = [rec(power="synthetic")]
    cmp = compare_sets(base, cur)
    by = {p.power_source: p.status for p in cmp.points}
    assert by == {"synthetic": UNCHANGED, "rapl": MISSING}
    assert cmp.exit_code(fail_on_regression=True) == 0
    assert cmp.exit_code(fail_on_missing=True) != 0


def test_report_notes_are_sanitized_for_csv_and_markdown():
    cur = [rec(metrics={}, status="error",
               error="RESOURCE_EXHAUSTED\nOut of memory, pipe | char")]
    cmp = compare_sets([rec()], cur)
    rows = cmp.points[0].flat()
    assert "\n" not in rows[0]["note"]
    assert "," not in rows[0]["note"] and "|" not in rows[0]["note"]
    assert len(cmp.to_csv().strip().splitlines()) == 2   # header + 1 row


def test_cli_rejects_negative_tolerances():
    from repro.bench.cli import _parse_tols
    with pytest.raises(SystemExit, match=">= 0"):
        _parse_tols("default=-1")
    assert _parse_tols("default=0") == {"default": 0.0}


def test_errored_point_surfaces_even_under_power_mismatch_dedup():
    """An errored record must report its crash even when the baseline
    holds the same point under a different power source — the mismatch
    dedup must not swallow the error."""
    base = [rec(power="synthetic")]
    cur = [rec(power="rapl", metrics={}, status="error", error="crash!")]
    cmp = compare_sets(base, cur)
    notes = " | ".join(p.note for p in cmp.points)
    assert "crash!" in notes
    assert any(p.status == REGRESSED for p in cmp.points)


def test_cli_promote_warns_about_stale_baseline_files(tmp_path, capsys):
    store = tmp_path / "baselines"
    old = _write_run(tmp_path, "old", 100.0)          # workload "wa"
    assert main(["compare", str(store), str(old), "--promote"]) == 0
    renamed = tmp_path / "renamed"
    save_records([rec(workload="wb", point={"bs": 1})], renamed / "wb")
    capsys.readouterr()
    main(["compare", str(store), str(renamed), "--promote"])
    err = capsys.readouterr().err
    assert "wa.json" in err and "removed or renamed" in err


def test_corrupt_records_fail_with_valueerror_not_typeerror(tmp_path):
    """Hand-edited documents must surface as the CLI's clean `error:`
    path (ValueError), never a raw TypeError/AttributeError traceback."""
    doc = {"schema_version": SCHEMA_VERSION, "workload": "w", "records": [
        {"point": {}, "schema_version": SCHEMA_VERSION}]}   # no workload
    p = tmp_path / "results.json"
    p.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="workload"):
        load_records(p)
    nulled = {"workload": "w", "point": {"bs": 1}, "noise": None,
              "schema_version": SCHEMA_VERSION}
    doc["records"] = [nulled]
    p.write_text(json.dumps(doc))
    (r,) = load_records(p)                  # null noise is tolerated...
    assert r.noise == {} and r.rel_std == 0.0
    nulled["metrics"] = "oops"              # ...but wrong types are not
    p.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="metrics"):
        load_records(p)


def test_power_mismatch_is_flagged_not_silently_joined():
    base = [rec(power="rapl")]
    cur = [rec(power="synthetic", metrics={"tokens_per_s": 1.0})]
    cmp = compare_sets(base, cur)
    (p,) = cmp.points                      # one row, not mismatch + new
    assert p.status == POWER_MISMATCH
    assert "rapl" in p.note and "synthetic" in p.note
    assert cmp.exit_code(fail_on_regression=True) != 0


def test_unknown_metrics_are_ignored_not_gated():
    base = [rec(metrics={"n_rows": 10, "tokens_per_s": 100.0})]
    cur = [rec(metrics={"n_rows": 3, "tokens_per_s": 100.0})]
    (p,) = compare_sets(base, cur).points
    assert p.status == UNCHANGED
    assert {d.metric for d in p.deltas} == {"tokens_per_s"}


def test_lost_metric_is_a_gated_regression():
    """A compared metric that vanishes (e.g. energy accounting broke)
    must fail the gate, not ride along as a footnote."""
    base = [rec(metrics={"tokens_per_s": 100.0, "wh_per_token": 1.0})]
    cur = [rec(metrics={"tokens_per_s": 100.0})]
    (p,) = compare_sets(base, cur).points
    assert p.status == REGRESSED
    assert "wh_per_token" in p.note


def test_new_point_that_errors_fails_the_gate():
    """A just-added point that errors every run must not hide behind
    `new` forever (it is never promoted, so it would stay green)."""
    cur = [rec(point={"bs": 9}, metrics={}, status="error", error="OOM")]
    cmp = compare_sets([rec()], cur)
    by = {p.point["bs"]: p for p in cmp.points}
    assert by[9].status == REGRESSED and "OOM" in by[9].note
    assert cmp.exit_code(fail_on_regression=True) != 0


def test_saturated_tolerance_still_catches_collapse():
    """Ratio-scale classification: even when noise widening pushes the
    threshold past 1.0 (where a relative delta bottoms out at -100%),
    an order-of-magnitude throughput collapse must still regress."""
    base = [rec(rel_std=1.0)]                     # capped to 0.5
    collapse = [rec(metrics={"tokens_per_s": 10.0}, rel_std=1.0)]
    halved = [rec(metrics={"tokens_per_s": 50.0}, rel_std=1.0)]
    tols = {"default": 0.6}                       # the CI gate's widening
    # tol = 0.6 + 2*0.5 = 1.6 -> regress beyond 2.6x worse
    assert compare_sets(base, collapse,
                        tols=tols).points[0].status == REGRESSED
    assert compare_sets(base, halved,
                        tols=tols).points[0].status == UNCHANGED


# ---------------------------------------------------------------------------
# tolerance model
# ---------------------------------------------------------------------------


def test_tolerance_widens_with_recorded_variance():
    base = [rec(rel_std=0.0)]
    # 30% drop: beyond the 20% base tolerance...
    quiet = [rec(metrics={"tokens_per_s": 70.0}, rel_std=0.0)]
    assert compare_sets(base, quiet).points[0].status == REGRESSED
    # ...but a run that itself wobbled 15% widens the gate past it
    noisy = [rec(metrics={"tokens_per_s": 70.0}, rel_std=0.15)]
    assert compare_sets(base, noisy).points[0].status == UNCHANGED
    # the noisier side wins regardless of which side recorded it
    noisy_base = [rec(rel_std=0.15)]
    assert compare_sets(noisy_base, quiet).points[0].status == UNCHANGED
    # noise_k=0 disables widening
    assert compare_sets(base, noisy,
                        noise_k=0.0).points[0].status == REGRESSED


def test_effective_tolerance_caps_noise_and_honors_overrides():
    a, b = rec(rel_std=0.0), rec(rel_std=5.0)   # absurd recorded spread
    tol = effective_tolerance("tokens_per_s", a, b, noise_k=2.0)
    assert tol == pytest.approx(0.20 + 2.0 * 0.5)   # capped at 0.5
    assert effective_tolerance("tokens_per_s", a, a,
                               tols={"tokens_per_s": 0.05}) == 0.05
    assert effective_tolerance("tokens_per_s", a, a,
                               tols={"default": 0.33}) == 0.33


def test_workload_declared_tolerances_stamp_and_outrank_cli_default(
        tmp_path):
    """A spec's compare_tols ride in record.noise and survive a blanket
    CLI --rel-tol default (the CI gate must not re-arm an exempted
    microbench); an explicit CLI per-metric override still wins."""
    spec = WorkloadSpec(name="toy_tols", analog="toy",
                        space=Space({"x": [1]}),
                        build=lambda pt, ctx: {
                            "run": lambda: {"us": 100.0}},
                        compare_tols={"default": float("inf")})
    (r,) = WorkloadRunner(spec, out_dir=str(tmp_path),
                          power="none").run(verbose=False)
    # inf is stamped as the string "inf": a bare `Infinity` literal would
    # make the committed baseline store non-RFC JSON
    assert r.noise["tols"] == {"default": "inf"}
    doc_text = (tmp_path / "toy_tols" / "results.json").read_text()
    json.loads(doc_text, parse_constant=lambda c: (_ for _ in ()).throw(
        ValueError(f"non-RFC JSON constant {c}")))
    (r,) = load_records(tmp_path / "toy_tols" / "results.json")
    slow = ResultRecord(workload="toy_tols", point={"x": 1},
                        metrics={"us": 900.0}, power_source="none",
                        noise=dict(r.noise))
    # 9x slower: exempted by the workload, even under a CLI default
    assert compare_sets([r], [slow]).points[0].status == UNCHANGED
    assert compare_sets([r], [slow],
                        tols={"default": 0.5}).points[0].status == UNCHANGED
    # an explicit per-metric CLI override re-arms the gate
    assert compare_sets([r], [slow],
                        tols={"us": 0.5}).points[0].status == REGRESSED


def test_diff_metric_zero_baseline_edge():
    assert diff_metric("tokens_per_s", 0.0, 0.0, 0.1).status == UNCHANGED
    assert diff_metric("tokens_per_s", 0.0, 5.0, 0.1).status == IMPROVED
    assert diff_metric("seconds", 0.0, 5.0, 0.1).status == REGRESSED


def test_degenerate_measurements_gate_as_regressions():
    """A Wh/time metric collapsing to exactly 0, or any NaN/inf value,
    is a broken measurement path — never 'improved' or 'unchanged'."""
    assert diff_metric("wh_per_token", 0.5, 0.0, 0.25).status == REGRESSED
    assert diff_metric("seconds", 1.0, 0.0, 0.2).status == REGRESSED
    nan, inf = float("nan"), float("inf")
    for bad in (nan, inf):
        assert diff_metric("tokens_per_s", 100.0, bad, 0.2
                           ).status == REGRESSED
        assert diff_metric("wh_per_token", bad, 0.5, 0.2
                           ).status == REGRESSED
    # even a tolerance-exempt workload (tol=inf) cannot launder NaN/zero
    assert diff_metric("us", 100.0, nan, inf).status == REGRESSED
    assert diff_metric("us", 100.0, 0.0, inf).status == REGRESSED
    # collapsing-to-zero *throughput* was already caught by the ratio path
    assert diff_metric("tokens_per_s", 100.0, 0.0, 0.2
                       ).status == REGRESSED


def test_equal_inf_is_unchanged_but_any_inf_transition_gates():
    """inf can be an honest value (wh_per_slo_request when nothing met
    the SLO): a cell saturated on BOTH sides is the same regime and
    must not flag forever, while entering or leaving inf is a regime
    change that gates until a human re-promotes."""
    inf = float("inf")
    same = diff_metric("wh_per_slo_request", inf, inf, 0.2)
    assert same.status == UNCHANGED
    assert diff_metric("wh_per_slo_request", 0.5, inf, 0.2
                       ).status == REGRESSED   # collapsed to inf
    assert diff_metric("wh_per_slo_request", inf, 0.5, 0.2
                       ).status == REGRESSED   # escaped inf: re-promote
    # opposite-sign infinities are NOT the same regime
    assert diff_metric("wh_per_slo_request", inf, -inf, 0.2
                       ).status == REGRESSED


def test_watchdog_rel_std_feeds_the_tolerance_model():
    w = StragglerWatchdog(warmup=3)
    assert w.rel_std() == 0.0
    for i, dt in enumerate([0.1, 0.2, 0.3]):
        w.observe(i, dt)
    assert 0.0 < w.rel_std() < 1.0


def test_runner_stamps_git_sha_and_noise(tmp_path):
    spec = WorkloadSpec(name="toy_cmp", analog="toy",
                        space=Space({"x": [1, 2]}),
                        build=lambda pt, ctx: {
                            "run": lambda: {"tokens_per_s": 10.0 * pt["x"]}})
    recs = WorkloadRunner(spec, out_dir=str(tmp_path),
                          power="none").run(verbose=False)
    for r in recs:
        assert r.schema_version == SCHEMA_VERSION
        assert "rel_std" in r.noise and r.noise["samples"] >= 1
        assert r.noise["source"] == "watchdog"   # build used no ctx.measure
        assert r.git_sha is None or len(r.git_sha) == 40
    # and the stamped records survive the save/load round trip
    assert load_records(tmp_path / "toy_cmp" / "results.json") == recs


def test_measure_split_spread_preferred_over_watchdog(tmp_path):
    """Workloads timed via ctx.measure get a *same-point* noise figure
    (split-window spread), not the watchdog's cross-point spread that
    mixes in sweep heterogeneity and saturates tolerances."""
    def build(pt, ctx):
        def run():
            m = ctx.measure(lambda: sum(range(2000)), power=False)
            return {"seconds": m.seconds}
        return {"run": run}

    spec = WorkloadSpec(name="toy_meas", analog="toy",
                        space=Space({"x": [1, 2, 3, 4]}), build=build)
    recs = WorkloadRunner(spec, out_dir=str(tmp_path), warmup=1, iters=4,
                          power="none").run(verbose=False)
    for r in recs:
        assert r.noise["source"] == "measure_split"
        assert 0.0 <= r.noise["rel_std"] < 1.0   # repetition noise, not
        # the orders-of-magnitude cross-point spread a sweep would show
    # a single timed window cannot estimate spread: it must fall back to
    # the watchdog, never fabricate a zero-noise "measure_split" claim
    recs1 = WorkloadRunner(spec, out_dir=str(tmp_path / "i1"),
                           power="none", iters=1).run(verbose=False)
    assert all(r.noise["source"] == "watchdog" for r in recs1)


# ---------------------------------------------------------------------------
# baseline store: promote -> compare round trip
# ---------------------------------------------------------------------------


def test_promote_compare_roundtrip(tmp_path):
    store = tmp_path / "baselines"
    recs = [rec(workload="wa", point={"bs": b}) for b in (1, 2)] + \
           [rec(workload="wb", point={"n": 1}, metrics={"seconds": 0.5}),
            rec(workload="wb", point={"n": 2}, status="error", error="x",
                metrics={})]
    written = promote(recs, store)
    assert [p.name for p in written] == ["wa.json", "wb.json"]
    back = load_result_set(store)
    assert len(back) == 3                  # error record not promoted
    cmp = compare_sets(back, recs)
    # the three promoted points round-trip unchanged; the error record
    # (never promoted) surfaces as a gated regression on the current side
    statuses = sorted(p.status for p in cmp.points)
    assert statuses == [REGRESSED, UNCHANGED, UNCHANGED, UNCHANGED]
    ok_only = [r for r in recs if r.ok]
    cmp_ok = compare_sets(back, ok_only)
    assert all(p.status == UNCHANGED for p in cmp_ok.points)
    assert cmp_ok.exit_code(fail_on_regression=True,
                            fail_on_missing=True) == 0
    # re-promoting one workload replaces only that file
    promote([rec(workload="wa", point={"bs": 1},
                 metrics={"tokens_per_s": 500.0})], store)
    assert len(load_result_set(store / "wa.json")) == 1
    assert len(load_result_set(store / "wb.json")) == 1


def test_load_result_set_layouts(tmp_path):
    r = [rec()]
    save_records(r, tmp_path / "run" / "w")        # runner tree layout
    assert load_result_set(tmp_path / "run") == r
    assert load_result_set(tmp_path / "run" / "w") == r
    assert load_result_set(tmp_path / "run" / "w" / "results.json") == r
    assert load_result_set(tmp_path / "does-not-exist") == []


# ---------------------------------------------------------------------------
# schema validation (report / load path)
# ---------------------------------------------------------------------------


def test_load_records_rejects_future_and_foreign_docs(tmp_path):
    p = tmp_path / "results.json"
    p.write_text(json.dumps({"schema_version": SCHEMA_VERSION + 7,
                             "records": []}))
    with pytest.raises(ValueError, match="schema_version"):
        load_records(p)
    p.write_text(json.dumps({"rows": []}))
    with pytest.raises(ValueError, match="results document"):
        load_records(p)
    p.write_text(json.dumps([{"workload": "w"}]))   # pre-schema list
    with pytest.raises(ValueError, match="legacy"):
        load_records(p)


def test_v1_records_upconvert_with_default_provenance(tmp_path):
    v1 = {"workload": "w", "point": {"bs": 8}, "metrics": {"seconds": 1.0},
          "power_source": "rapl", "n_devices": 1, "attempts": 1,
          "status": "ok", "error": None, "schema_version": 1}
    p = tmp_path / "results.json"
    p.write_text(json.dumps({"schema_version": 1, "workload": "w",
                             "records": [v1]}))
    (r,) = load_records(p)
    assert r.git_sha is None and r.noise == {} and r.rel_std == 0.0
    # and it joins/compares fine against v2 records
    cur = rec(workload="w", point={"bs": 8}, metrics={"seconds": 1.0},
              power="rapl")
    assert compare_sets([r], [cur]).points[0].status == UNCHANGED


def test_report_cli_rejects_bad_schema_clearly(tmp_path, capsys):
    bad = tmp_path / "mystery" / "results.json"
    bad.parent.mkdir(parents=True)
    bad.write_text(json.dumps({"schema_version": 99, "records": []}))
    assert main(["report", "--out", str(tmp_path)]) == 2
    err = capsys.readouterr().err
    assert "schema_version" in err and "99" in err


# ---------------------------------------------------------------------------
# CLI: compare / --promote / --fail-on-regression
# ---------------------------------------------------------------------------


def _write_run(tmp_path, name, tps):
    out = tmp_path / name
    save_records([rec(workload="wa", point={"bs": 1},
                      metrics={"tokens_per_s": tps})], out / "wa")
    return out


def test_cli_compare_gates_on_regression(tmp_path, capsys):
    base = _write_run(tmp_path, "base", 100.0)
    good = _write_run(tmp_path, "good", 98.0)
    bad = _write_run(tmp_path, "bad", 40.0)
    assert main(["compare", str(base), str(good),
                 "--fail-on-regression"]) == 0
    capsys.readouterr()
    rc = main(["compare", str(base), str(bad), "--fail-on-regression"])
    assert rc != 0
    cap = capsys.readouterr()
    assert "regressed" in cap.out and "GATE" in cap.err
    # without the flag the diff is informational
    assert main(["compare", str(base), str(bad)]) == 0


def test_cli_compare_promote_and_reports(tmp_path, capsys):
    run = _write_run(tmp_path, "run", 100.0)
    store = tmp_path / "baselines"
    assert main(["compare", str(store), str(run), "--promote"]) == 0
    assert (store / "wa.json").exists()
    capsys.readouterr()
    # now the committed store gates an identical re-run green
    report = tmp_path / "report.md"
    assert main(["compare", str(store), str(run), "--fail-on-regression",
                 "--fail-on-missing", "--report-out", str(report)]) == 0
    assert "unchanged" in report.read_text()
    # csv report renders rows
    assert main(["compare", str(store), str(run), "--report", "csv",
                 "--all-points"]) == 0
    assert "workload" in capsys.readouterr().out
    # custom tolerance flips a mild delta into a regression
    mild = _write_run(tmp_path, "mild", 90.0)
    assert main(["compare", str(store), str(mild), "--fail-on-regression",
                 "--rel-tol", "tokens_per_s=0.01", "--noise-k", "0"]) != 0


def test_cli_compare_rejects_empty_current_set(tmp_path, capsys):
    """A typo'd run dir must not read as 'nothing regressed'."""
    base = _write_run(tmp_path, "base", 100.0)
    assert main(["compare", str(base), str(tmp_path / "typo"),
                 "--fail-on-regression"]) == 2
    assert "nothing to compare" in capsys.readouterr().err


def test_cli_promote_warns_on_all_error_workload(tmp_path, capsys):
    store = tmp_path / "baselines"
    good = _write_run(tmp_path, "good", 100.0)
    assert main(["compare", str(store), str(good), "--promote"]) == 0
    before = (store / "wa.json").read_text()
    broken = tmp_path / "broken"
    save_records([rec(workload="wa", point={"bs": 1}, metrics={},
                      status="error", error="boom")], broken / "wa")
    capsys.readouterr()
    main(["compare", str(store), str(broken), "--promote"])
    cap = capsys.readouterr()
    assert "NOT promoted" in cap.err
    assert (store / "wa.json").read_text() == before   # old baseline stands


def test_cli_compare_missing_gate(tmp_path):
    base = tmp_path / "base"
    save_records([rec(workload="wa", point={"bs": 1}),
                  rec(workload="wa", point={"bs": 2})], base / "wa")
    cur = _write_run(tmp_path, "cur", 100.0)   # only bs=1
    assert main(["compare", str(base), str(cur)]) == 0
    assert main(["compare", str(base), str(cur),
                 "--fail-on-missing"]) != 0


def test_cli_gate_lines_name_only_gated_statuses(tmp_path, capsys):
    """CI logs must not send readers chasing statuses the active flags
    did not actually gate on."""
    base = tmp_path / "base"
    save_records([rec(workload="wa", point={"bs": 1}),
                  rec(workload="wa", point={"bs": 2})], base / "wa")
    cur = tmp_path / "cur"                    # bs=1 regressed, bs=2 gone
    save_records([rec(workload="wa", point={"bs": 1},
                      metrics={"tokens_per_s": 10.0})], cur / "wa")
    assert main(["compare", str(base), str(cur),
                 "--fail-on-missing"]) != 0
    err = capsys.readouterr().err
    assert "GATE: missing" in err and "GATE: regressed" not in err
    assert main(["compare", str(base), str(cur),
                 "--fail-on-regression"]) != 0
    err = capsys.readouterr().err
    assert "GATE: regressed" in err and "GATE: missing" not in err


# ---------------------------------------------------------------------------
# power autoselection fallback chain -> labels land in records
# ---------------------------------------------------------------------------


def _run_auto(tmp_path, name):
    spec = WorkloadSpec(name=name, analog="toy", space=Space({"x": [1]}),
                        build=lambda pt, ctx: {
                            "run": lambda: {"tokens_per_s": 1.0}})
    (r,) = WorkloadRunner(spec, out_dir=str(tmp_path),
                          power="auto").run(verbose=False)
    return r


def test_power_fallback_chain_end_to_end(tmp_path, monkeypatch):
    """TPU platform -> TPU model (even with RAPL present), else RAPL,
    else synthetic, with the winning label stamped into the records
    compare joins on. The platform is steered through
    ``core.device.current``."""
    from repro.core import device
    zone = tmp_path / "powercap" / "intel-rapl:0"
    zone.mkdir(parents=True)
    (zone / "energy_uj").write_text("123456\n")
    monkeypatch.setattr(RaplPower, "ROOT", str(tmp_path / "powercap"))
    dry = tmp_path / "dryrun"
    dry.mkdir()
    (dry / "step.json").write_text(
        json.dumps({"roofline": {"roofline_fraction": 0.5}}))
    monkeypatch.setenv("REPRO_DRYRUN_DIR", str(dry))
    cpu = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite",
           "device_count": 2}
    # stage 1: a TPU platform -> analytic model, RAPL notwithstanding
    monkeypatch.setattr(device, "current", lambda: tpu)
    methods, src = select_power_methods("auto", n_devices=2)
    assert src == "tpu_model" and len(methods[0].devices()) == 2
    r1 = _run_auto(tmp_path / "o1", "toy_tpu")
    assert r1.power_source == "tpu_model"
    assert r1.metrics.get("tokens_per_s") == 1.0
    # stage 2: CPU platform with powercap sysfs present -> rapl
    monkeypatch.setattr(device, "current", lambda: cpu)
    r2 = _run_auto(tmp_path / "o2", "toy_rapl")
    assert r2.power_source == "rapl"
    # stage 3: no RAPL, no TPU -> deterministic synthetic floor
    monkeypatch.setattr(RaplPower, "ROOT", str(tmp_path / "empty"))
    r3 = _run_auto(tmp_path / "o3", "toy_synth")
    assert r3.power_source == "synthetic"
    # the three labels never join silently: same point, disjoint keys
    keys = {point_key(ResultRecord(workload="t", point={"x": 1},
                                   power_source=r.power_source))
            for r in (r1, r2, r3)}
    assert len(keys) == 3
    cmp = compare_sets(
        [ResultRecord(workload="t", point={"x": 1}, power_source="rapl",
                      metrics={"tokens_per_s": 1.0})],
        [ResultRecord(workload="t", point={"x": 1},
                      power_source="synthetic",
                      metrics={"tokens_per_s": 1.0})])
    assert cmp.points[0].status == POWER_MISMATCH


def test_write_result_doc_is_loadable_and_versioned(tmp_path):
    path = tmp_path / "nested" / "wa.json"
    write_result_doc([rec(workload="wa")], path)
    doc = json.loads(path.read_text())
    assert doc["schema_version"] == SCHEMA_VERSION
    assert doc["workload"] == "wa"
    assert load_records(path) == [rec(workload="wa")]
