"""jpwr-analog power measurement: integration properties (hypothesis),
method plumbing, suffix interpolation, export."""
import json
import math
import time

import pytest
from _prop import given, settings, st

from repro.core import device
from repro.power.ctxmgr import MeasuredScope, expand_suffix, get_power
from repro.power.frame import Frame
from repro.power.methods import (
    RaplPower, SyntheticPower, TPUModelPower, get_method,
    select_power_methods,
)
from repro.power.utilization import roofline_utilization_fn


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_constant_power_energy_exact():
    """Property: integrating constant power P over T seconds = P*T J."""
    clock = FakeClock()
    m = SyntheticPower(n_devices=2, base=150.0, amp=0.0, clock=clock)
    scope = MeasuredScope([m], interval_ms=1e9, clock=clock)  # manual sample
    scope._sample()
    for t in (1.0, 2.0, 3.0):
        clock.t = t
        scope._sample()
    edf, _ = scope.energy()
    for r in edf.records():
        assert math.isclose(r["energy_wh"], 150.0 * 3.0 / 3600.0,
                            rel_tol=1e-9)
        assert math.isclose(r["avg_power_w"], 150.0, rel_tol=1e-9)


@settings(max_examples=20, deadline=None)
@given(base=st.floats(10, 500), slope=st.floats(0, 100),
       n=st.integers(2, 50))
def test_linear_power_trapezoid_exact(base, slope, n):
    """Property: trapezoid integration is exact for linear P(t)."""
    clock = FakeClock()

    class Linear(SyntheticPower):
        def read(self):
            return {"d0": base + slope * clock()}

        def devices(self):
            return ["d0"]

    scope = MeasuredScope([Linear()], interval_ms=1e9, clock=clock)
    for i in range(n + 1):
        clock.t = i / n
        scope._sample()
    edf, _ = scope.energy()
    want_j = base * 1.0 + slope * 0.5  # integral over [0, 1]
    assert math.isclose(edf.records()[0]["energy_wh"], want_j / 3600,
                        rel_tol=1e-9)


def test_background_thread_sampling():
    with get_power([SyntheticPower(n_devices=1, base=100.0)],
                   interval_ms=5) as scope:
        time.sleep(0.08)
    assert len(scope.df) >= 5
    e = scope.total_energy_wh()
    assert e > 0


V5E = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}


def test_tpu_model_power_utilization():
    util = {"v": 0.0}
    m = TPUModelPower(n_devices=4, utilization_fn=lambda: util["v"],
                      kind="TPU v5 lite")
    assert all(abs(w - 60.0) < 1e-9 for w in m.read().values())
    util["v"] = 1.0
    assert all(abs(w - 220.0) < 1e-9 for w in m.read().values())
    util["v"] = 0.5
    assert all(abs(w - 140.0) < 1e-9 for w in m.read().values())


def test_rapl_graceful_when_absent():
    m = RaplPower(root="/nonexistent/powercap")
    assert not m.available()
    assert m.read() == {}


def _fake_powercap(tmp_path, uj: float):
    zone = tmp_path / "intel-rapl:0"
    zone.mkdir(exist_ok=True)
    (zone / "energy_uj").write_text(f"{int(uj)}\n")
    return tmp_path


def test_rapl_reads_fake_powercap_tree(tmp_path, monkeypatch):
    import repro.power.methods as pm

    fake_t = {"t": 100.0}
    monkeypatch.setattr(pm.time, "monotonic", lambda: fake_t["t"])
    root = _fake_powercap(tmp_path, 1_000_000)
    m = RaplPower(root=str(root))
    assert m.available()
    assert m.read() == {"intel-rapl:0": 0.0}   # first read: no baseline
    _fake_powercap(tmp_path, 3_000_000)        # +2 J over 2 s -> 1 W
    fake_t["t"] = 102.0
    assert m.read()["intel-rapl:0"] == pytest.approx(1.0)


def test_rapl_counter_wrap_uses_post_wrap_delta(tmp_path, monkeypatch):
    """Regression: when energy_uj wraps (new < old), read() must treat
    the post-wrap counter value as the energy delta — not report a
    negative (or bogus huge) power."""
    import repro.power.methods as pm

    fake_t = {"t": 50.0}
    monkeypatch.setattr(pm.time, "monotonic", lambda: fake_t["t"])
    root = _fake_powercap(tmp_path, 10_000_000)
    m = RaplPower(root=str(root))
    m.read()                                   # baseline at 10 J
    _fake_powercap(tmp_path, 4_000_000)        # counter wrapped to 4 J
    fake_t["t"] = 52.0
    w = m.read()["intel-rapl:0"]
    assert w == pytest.approx(4_000_000 / 2.0 / 1e6)  # 2 W, not negative
    assert w >= 0.0


def test_suffix_interpolation(monkeypatch):
    monkeypatch.setenv("SLURM_PROCID", "7")
    assert expand_suffix("_%q{SLURM_PROCID}") == "_7"
    assert expand_suffix("_%q{MISSING_VAR_XYZ}") == "_"


def test_export_csv(tmp_path):
    with get_power([SyntheticPower(n_devices=1)], interval_ms=5) as scope:
        time.sleep(0.02)
    scope.export(str(tmp_path), "csv", suffix="_r0")
    assert (tmp_path / "power_r0.csv").exists()
    assert (tmp_path / "energy_r0.csv").exists()
    text = (tmp_path / "energy_r0.csv").read_text()
    assert "energy_wh" in text


def test_frame_roundtrip():
    f = Frame.from_records([{"a": 1, "b": 2.5}, {"a": 3, "b": None}])
    assert f.col("a") == [1, 3]
    csv = f.to_csv()
    assert csv.splitlines()[0] == "a,b"
    assert len(f) == 2


# ---------------------------------------------------------------------------
# Roofline-grounded utilization for the analytic TPU model (ISSUE 6:
# the old utilization_fn was a constant 1.0 — full TDP for every cell)
# ---------------------------------------------------------------------------


def _dryrun_artifact(path, frac):
    path.write_text(json.dumps({"roofline": {"roofline_fraction": frac}}))


def test_roofline_utilization_averages_dryrun_fractions(tmp_path):
    _dryrun_artifact(tmp_path / "a.json", 0.2)
    _dryrun_artifact(tmp_path / "b.json", 0.6)
    _dryrun_artifact(tmp_path / "c.json", 7.0)    # clamps to 1.0
    (tmp_path / "junk.json").write_text("not json")          # skipped
    (tmp_path / "other.json").write_text('{"no": "roofline"}')
    fn = roofline_utilization_fn(dryrun_dir=str(tmp_path))
    assert fn() == pytest.approx((0.2 + 0.6 + 1.0) / 3)


def test_roofline_utilization_falls_back_with_warning(tmp_path):
    """No roofline data: no fallback to a constant (which billed full
    TDP on a TPU), an error that names where it looked."""
    with pytest.raises(FileNotFoundError, match="missing"):
        roofline_utilization_fn(dryrun_dir=str(tmp_path / "missing"))


def test_tpu_model_selection_wires_roofline_occupancy(tmp_path,
                                                     monkeypatch):
    """select_power_methods(prefer='tpu_model') must bill at roofline
    occupancy, not constant TDP, when dry-run artifacts exist."""
    monkeypatch.setattr(device, "current", lambda: V5E)
    monkeypatch.setenv("REPRO_DRYRUN_DIR", str(tmp_path))
    _dryrun_artifact(tmp_path / "step.json", 0.25)
    methods, label = select_power_methods("tpu_model", n_devices=2)
    assert label == "tpu_model"
    (m,) = methods
    assert m.utilization_fn() == pytest.approx(0.25)
    want_w = m.idle_w + (m.tdp_w - m.idle_w) * 0.25
    assert all(w == pytest.approx(want_w) for w in m.read().values())
    assert want_w < m.tdp_w                      # no longer full-TDP


def test_tpu_platform_picks_tpu_model_even_with_rapl(tmp_path, monkeypatch):
    """On a TPU the host's RAPL counters measure the host CPU, not the
    chip: auto must pick the device-kind model, with that kind's
    envelope, and never RAPL."""
    monkeypatch.setattr(RaplPower, "ROOT", str(_fake_powercap(tmp_path,
                                                              1_000_000)))
    assert RaplPower().available()
    monkeypatch.setattr(device, "current", lambda: V5E)
    methods, label = select_power_methods(
        "auto", n_devices=2, utilization_fn=lambda: 0.5)
    assert label == "tpu_model"
    (m,) = methods
    spec = device.chip("TPU v5 lite")
    assert (m.tdp_w, m.idle_w) == (spec.tdp_w, spec.idle_w)
    assert len(m.devices()) == 2
    # off the TPU the same host's RAPL wins again
    monkeypatch.setattr(device, "current", lambda: {
        "platform": "cpu", "device_kind": "cpu", "device_count": 1})
    assert select_power_methods("auto")[1] == "rapl"


def test_tpu_model_of_unknown_device_kind_is_an_error(monkeypatch):
    with pytest.raises(KeyError, match="TPU v9"):
        TPUModelPower(kind="TPU v9")
    monkeypatch.setattr(device, "current", lambda: {
        "platform": "tpu", "device_kind": "TPU v9", "device_count": 1})
    with pytest.raises(KeyError, match="TPU v9"):
        select_power_methods("auto", utilization_fn=lambda: 0.5)
