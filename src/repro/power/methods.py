"""Power-measurement methods (jpwr's pluggable backend architecture).

Each method exposes ``read() -> dict[device_name, watts]`` plus metadata.
Methods mirror jpwr's: where jpwr has pynvml / rocm-smi / gcipuinfo /
GH200-sysfs, we provide:

  rapl       — Linux powercap sysfs (real counters where the host has them;
               the direct analog of jpwr's `gh` hwmon-sysfs method)
  tpu_model  — analytic TPU power model driven by a utilization source
               (roofline occupancy of the compiled step), with the
               envelope of the device kind from ``core.device.CHIPS``;
               TPUs expose no user-space power counter
  synthetic  — deterministic waveform, for tests and CI
"""
from __future__ import annotations

import os
import time
from typing import Callable, Optional

from repro.core import device


class PowerMethod:
    name = "base"

    def devices(self) -> list[str]:
        raise NotImplementedError

    def read(self) -> dict[str, float]:
        """Instantaneous power per device, in watts."""
        raise NotImplementedError

    def available(self) -> bool:
        return True


class SyntheticPower(PowerMethod):
    """Deterministic device power: P(t) = base + amp * tri(t/period)."""

    name = "synthetic"

    def __init__(self, n_devices: int = 1, base: float = 100.0,
                 amp: float = 0.0, period: float = 1.0,
                 clock: Callable[[], float] = time.monotonic):
        self.n, self.base, self.amp, self.period = n_devices, base, amp, period
        self.clock = clock
        self._t0 = clock()

    def devices(self):
        return [f"synthetic:{i}" for i in range(self.n)]

    def read(self):
        t = (self.clock() - self._t0) / self.period
        tri = abs(2 * (t - int(t)) - 1)  # triangle wave in [0, 1]
        p = self.base + self.amp * tri
        return {d: p for d in self.devices()}


class RaplPower(PowerMethod):
    """Linux powercap (intel-rapl) sysfs energy counters -> watts."""

    name = "rapl"
    ROOT = "/sys/class/powercap"

    def __init__(self, root: Optional[str] = None):
        self.root = root or self.ROOT
        self._zones = []
        if os.path.isdir(self.root):
            for z in sorted(os.listdir(self.root)):
                p = os.path.join(self.root, z, "energy_uj")
                if os.path.exists(p):
                    self._zones.append((z, p))
        self._last: dict[str, tuple[float, float]] = {}

    def available(self) -> bool:
        if not self._zones:
            return False
        try:
            with open(self._zones[0][1]) as f:
                f.read()
            return True
        except OSError:
            return False

    def devices(self):
        return [z for z, _ in self._zones]

    def read(self):
        out = {}
        now = time.monotonic()
        for z, p in self._zones:
            try:
                with open(p) as f:
                    uj = float(f.read().strip())
            except OSError:
                continue
            if z in self._last:
                uj0, t0 = self._last[z]
                dt = max(now - t0, 1e-6)
                d_uj = uj - uj0
                if d_uj < 0:  # counter wrap
                    d_uj = uj
                out[z] = d_uj / dt / 1e6
            else:
                out[z] = 0.0
            self._last[z] = (uj, now)
        return out


class TPUModelPower(PowerMethod):
    """Analytic TPU power: P = P_idle + (P_TDP - P_idle) * utilization.

    ``utilization_fn`` is supplied by the benchmark runner: typically the
    roofline occupancy of the running step (compute_term / step_time from
    the dry-run artifact), or a live duty-cycle estimate. ``kind`` is
    the ``device_kind`` whose envelope (``core.device.chip``) applies;
    an unknown kind raises KeyError.
    """

    name = "tpu_model"

    def __init__(self, n_devices: int = 1,
                 utilization_fn: Optional[Callable[[], float]] = None, *,
                 kind: str):
        spec = device.chip(kind)
        self.n = n_devices
        self.utilization_fn = utilization_fn or (lambda: 0.0)
        self.tdp_w, self.idle_w = spec.tdp_w, spec.idle_w

    def devices(self):
        return [f"tpu:{i}" for i in range(self.n)]

    def read(self):
        u = min(max(float(self.utilization_fn()), 0.0), 1.0)
        p = self.idle_w + (self.tdp_w - self.idle_w) * u
        return {d: p for d in self.devices()}


class FallbackPower(PowerMethod):
    """Resilience wrapper: a primary backend whose ``read()`` failures
    fall back to a second method instead of crashing (or silently
    zeroing) the measurement.

    Column stability: ``name``/``devices()`` are the PRIMARY's —
    ``MeasuredScope`` builds its frame columns once at entry, so the
    wrapper must look like the primary forever. Fallback readings are
    remapped onto the primary's device names (total watts split evenly).
    After ``max_failures`` consecutive primary failures the wrapper
    stops poking the dead backend (``degraded``). ``label`` reports
    ``"<primary>+fallback:<name>"`` once any fallback reading was used,
    so records never pass modeled power off as measured.
    """

    def __init__(self, primary: PowerMethod, fallback: PowerMethod,
                 max_failures: int = 3):
        self.primary, self.fallback = primary, fallback
        self.name = primary.name
        self.max_failures = max(1, int(max_failures))
        self.failures = 0           # consecutive primary read failures
        self.fallback_reads = 0
        self.degraded = False

    @property
    def label(self) -> str:
        if self.fallback_reads:
            return f"{self.primary.name}+fallback:{self.fallback.name}"
        return self.primary.name

    def devices(self):
        return self.primary.devices()

    def available(self) -> bool:
        return self.primary.available() or self.fallback.available()

    def _read_fallback(self) -> dict:
        self.fallback_reads += 1
        vals = self.fallback.read()
        devs = self.primary.devices()
        per = sum(vals.values()) / max(len(devs), 1)
        return {d: per for d in devs}

    def read(self) -> dict:
        if self.degraded:
            return self._read_fallback()
        try:
            out = self.primary.read()
            self.failures = 0
            return out
        except Exception:  # noqa: BLE001 - a dead backend must not crash
            self.failures += 1
            if self.failures >= self.max_failures:
                self.degraded = True
            return self._read_fallback()


METHODS = {"synthetic": SyntheticPower, "rapl": RaplPower,
           "tpu_model": TPUModelPower}


def get_method(name: str, **kw) -> PowerMethod:
    return METHODS[name](**kw)


def select_power_methods(prefer: str = "auto", *, n_devices: int = 1,
                         utilization_fn: Optional[Callable[[], float]] = None,
                         ) -> tuple[list[PowerMethod], str]:
    """Pick the measurement backend for this process, returning
    ``(methods, source_label)``.

    The label is stamped into every result record as ``power_source`` so a
    reader can always tell measured counters from modeled or synthetic
    power. ``prefer`` forces a specific backend (or ``"none"`` to disable
    measurement); ``"auto"`` decides from the platform JAX runs on
    (``core.device.current``):

      tpu_model  — on a TPU: the analytic model of that device kind
                   (TPUs expose no user-space counter; the host's RAPL
                   counters would bill the host CPU as the run's energy)
      rapl       — elsewhere, real powercap counters when the host
                   exposes them
      synthetic  — deterministic waveform everywhere else (CPU CI hosts),
                   so energy columns stay populated but clearly labeled
    """
    if prefer == "none":
        return [], "none"
    if prefer not in ("auto", None):
        if prefer not in METHODS:
            raise KeyError(f"unknown power method {prefer!r}; "
                           f"known: {sorted(METHODS)} + ['auto', 'none']")
        kw: dict = {}
        if prefer in ("synthetic", "tpu_model"):
            kw["n_devices"] = n_devices
        if prefer == "tpu_model":
            return [_tpu_model(n_devices, utilization_fn)], prefer
        return [METHODS[prefer](**kw)], prefer
    if device.current()["platform"] == "tpu":
        return [_tpu_model(n_devices, utilization_fn)], "tpu_model"
    rapl = RaplPower()
    if rapl.available():
        return [rapl], "rapl"
    return [SyntheticPower(n_devices=n_devices)], "synthetic"


def _tpu_model(n_devices: int,
               utilization_fn: Optional[Callable[[], float]]) -> TPUModelPower:
    """The analytic model of this process's device kind. Without an
    explicit ``utilization_fn`` it bills the roofline occupancy of the
    dry-run artifacts, and raises where there are none."""
    from repro.power.utilization import roofline_utilization_fn
    return TPUModelPower(
        n_devices=n_devices,
        utilization_fn=utilization_fn or roofline_utilization_fn(),
        kind=device.current()["device_kind"])
