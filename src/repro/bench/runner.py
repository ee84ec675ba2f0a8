"""WorkloadRunner — executes one WorkloadSpec end to end.

Extends the core suite runner machinery (`repro.core.runner.run_attempts`)
to the declarative WorkloadSpec contract: expand the point space (smoke
preset / ``--points`` overrides applied), select the power backend once
(RAPL -> TPU-model -> synthetic, labeled), call ``spec.build`` per point,
run each returned step thunk with retries and straggler detection, and
persist normalized ``ResultRecord``s incrementally + a manifest.

Placement-aware: each point resolves its device mesh via
``spec.placement_for`` (the ``placement`` Space axis, else the spec
default). The runner hands the resolved :class:`~repro.bench.spec.Placement`
to the build through ``ctx.placement``/``ctx.mesh()``, sizes the power
backend to the point's mesh (per-device attribution: a dp4 cell is
billed four devices' watts, not one), and stamps the cross-placement
scaling metrics (``records.stamp_scaling_metrics``) into every sweep.
On a CPU host a point whose mesh exceeds the local device count is not
an error: the runner renders a ``launch.slurm`` job script sized to the
mesh and records the point as ``deferred`` — the sweep's local cells
still measure, and the script carries the oversized cell to the
cluster. On an accelerator such a point is an error: the chips present
are the host under test.
"""
from __future__ import annotations

import math
import os
import pathlib
import re
import time
from typing import Optional, Sequence

from repro.bench.context import RunContext
from repro.bench.records import (
    ResultRecord, save_records, stamp_scaling_metrics,
)
from repro.bench.spec import Placement, WorkloadSpec
from repro.core.manifest import git_sha, write_manifest
from repro.core.results import table
from repro.core.runner import StragglerWatchdog, run_attempts
from repro.launch.slurm import render_bench_job
from repro.power.methods import PowerMethod, select_power_methods


def _emulation_device_cap() -> Optional[int]:
    """Physical-core cap for scaling metrics when the "devices" are
    forced host-platform fakes (``--xla_force_host_platform_device_count``
    on a CPU backend): N fake devices share ``cores`` real cores, so
    per-device figures normalize by ``min(n, cores)``. Returns None on
    real accelerators (or single-device CPU) — classic semantics."""
    try:
        import jax
        if jax.default_backend() != "cpu" or jax.device_count() <= 1:
            return None
    except Exception:
        return None
    try:
        return len(os.sched_getaffinity(0)) or None
    except (AttributeError, OSError):
        return os.cpu_count()


class WorkloadRunner:
    def __init__(self, spec: WorkloadSpec, *,
                 out_dir: str = "artifacts/bench",
                 power: str = "auto",
                 power_methods: Optional[Sequence[PowerMethod]] = None,
                 power_source: Optional[str] = None,
                 warmup: int = 1, iters: int = 3,
                 smoke: bool = False,
                 point_overrides: Optional[dict] = None,
                 retries: int = 1,
                 power_interval_ms: float = 20.0):
        self.spec = spec
        self.out = pathlib.Path(out_dir) / spec.name
        self.smoke = smoke
        self.point_overrides = point_overrides
        self._power_arg = power
        self._power_injected = power_methods is not None
        self._power_by_n: dict[int, list] = {}
        if self._power_injected:
            self.power_methods = list(power_methods)
            self.power_source = power_source or (
                self.power_methods[0].name if self.power_methods else "none")
        else:
            n = spec.max_devices(smoke, point_overrides)
            self.power_methods, self.power_source = select_power_methods(
                power, n_devices=n)
            self._power_by_n[n] = self.power_methods
        self.warmup = warmup
        self.iters = iters
        self.retries = retries
        self.power_interval_ms = power_interval_ms
        self.watchdog = StragglerWatchdog()
        self.records: list[ResultRecord] = []

    def _power_for(self, n_devices: int) -> list:
        """Power methods sized to one point's mesh — per-device energy
        attribution for placement sweeps. Injected methods (tests, a
        caller-owned scope) are used as-is."""
        if self._power_injected:
            return self.power_methods
        if n_devices not in self._power_by_n:
            self._power_by_n[n_devices], _ = select_power_methods(
                self._power_arg, n_devices=n_devices)
        return self._power_by_n[n_devices]

    def run(self, verbose: bool = True) -> list[ResultRecord]:
        spec = self.spec
        self.out.mkdir(parents=True, exist_ok=True)
        write_manifest(self.out, {
            "workload": spec.name, "analog": spec.analog,
            "placement": spec.placement.label,
            "max_devices": spec.max_devices(self.smoke,
                                            self.point_overrides),
            "tags": sorted(spec.tags),
            "power_source": self.power_source, "smoke": self.smoke,
        })
        ctx = RunContext(out_dir=self.out,
                         power_methods=self.power_methods,
                         power_source=self.power_source,
                         power_interval_ms=self.power_interval_ms,
                         warmup=self.warmup, iters=self.iters,
                         smoke=self.smoke)
        points = spec.space_for(self.smoke, self.point_overrides).expand()
        for i, pt in enumerate(points):
            rec = self._run_point(pt, ctx)
            self.records.append(rec)
            if verbose:
                print(f"[{spec.name}] {i + 1}/{len(points)} {rec.flat()}",
                      flush=True)
            # scaling metrics join cells ACROSS the sweep (each scaled
            # cell against its 1-device twin), so re-derive over the
            # whole record list before each incremental save
            stamp_scaling_metrics(self.records,
                                  device_cap=_emulation_device_cap())
            save_records(self.records, self.out)
        return self.records

    def _defer_point(self, pt: dict, placement: Placement,
                     rec: ResultRecord, have: int) -> ResultRecord:
        """Render the Slurm script that carries an oversized mesh to the
        cluster; the record keeps the sweep's bookkeeping honest."""
        # one script PER POINT (the placement label alone would let
        # same-mesh cells of a sweep clobber each other), forwarding
        # this run's power/out/warmup/iters so the cluster record joins
        # the local result set by point key
        slug = "_".join(f"{k}{pt[k]}" for k in sorted(pt)
                        if k != "placement")
        slug = re.sub(r"[^A-Za-z0-9._-]+", "-", slug)
        name = f"{self.spec.name}_{placement.label}" + (f"_{slug}" if slug
                                                        else "")
        power = self._power_arg if not self._power_injected \
            else self.power_source
        script = render_bench_job(workload=self.spec.name,
                                  placement=placement, point=pt,
                                  out=str(self.out.parent), power=power,
                                  warmup=self.warmup, iters=self.iters,
                                  job_suffix=f"_{slug}" if slug else "")
        path = self.out / "slurm" / f"{name}.sbatch"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(script)
        rec.status = "deferred"
        rec.error = (f"mesh {placement.label} needs {placement.n_devices} "
                     f"devices, process has {have}; sbatch script rendered "
                     f"to {path}")
        rec.metrics["slurm_script"] = str(path)
        return rec

    def _run_point(self, pt: dict, ctx: RunContext) -> ResultRecord:
        spec = self.spec
        ctx.last_measurement = None
        placement = spec.placement_for(pt)
        rec = ResultRecord(workload=spec.name, point=dict(pt),
                           power_source=self.power_source,
                           placement=placement.dict(),
                           git_sha=git_sha())
        import jax
        have = jax.device_count()
        if placement.n_devices > have:
            if jax.default_backend() == "cpu":
                return self._defer_point(pt, placement, rec, have)
            rec.status = "error"
            rec.error = (f"mesh {placement.label} needs "
                         f"{placement.n_devices} devices, this "
                         f"{jax.default_backend()} host has {have}")
            return rec
        ctx.placement = placement
        ctx.power_methods = self._power_for(placement.n_devices)
        t0 = time.perf_counter()
        backoff_total = 0.0
        ok, step_fns, info = run_attempts(
            "build", lambda: spec.build(pt, ctx), self.retries,
            log_prefix=f"[{spec.name}] ", backoff_base=0.05)
        rec.attempts = info.attempts
        backoff_total += info.backoff_s
        if not ok:
            rec.status, rec.error = "error", step_fns["build_error"]
            return rec
        for name, fn in step_fns.items():
            ok, metrics, info = run_attempts(
                name, fn, self.retries, log_prefix=f"[{spec.name}] ",
                backoff_base=0.05)
            rec.attempts = max(rec.attempts, info.attempts)
            backoff_total += info.backoff_s
            if not ok:
                rec.status, rec.error = "error", metrics[f"{name}_error"]
                break
            rec.metrics.update(metrics or {})
        if backoff_total > 0.0 or rec.attempts > 1:
            rec.metrics["retry_backoff_s"] = round(backoff_total, 6)
        dt = time.perf_counter() - t0
        if self.watchdog.observe(len(self.records), dt):
            rec.metrics["straggler"] = True
        # tolerance inputs for `repro.bench compare`: prefer the split
        # timed-window spread of this point's own ctx.measure call (pure
        # repetition noise); the watchdog's warmup-seeded spread is the
        # fallback for workloads that orchestrate their own timing, and
        # mixes in cross-point sweep heterogeneity (hence the cap in
        # compare.effective_tolerance)
        m = ctx.last_measurement
        if m is not None and m.rel_spread is not None:
            # two timed half-windows back this estimate, not the
            # watchdog's cross-point count
            rel_std, noise_src, samples = m.rel_spread, "measure_split", 2
        else:
            rel_std, noise_src, samples = (self.watchdog.rel_std(),
                                           "watchdog", self.watchdog.n)
        rec.noise = {"rel_std": round(rel_std, 6), "source": noise_src,
                     "samples": samples,
                     "point_seconds": round(dt, 6)}
        if spec.compare_tols:
            # non-finite floats would serialize as bare `Infinity` — not
            # RFC JSON, and the baseline store is a committed, diffable
            # artifact; "inf" parses back via float() in compare
            rec.noise["tols"] = {
                k: v if isinstance(v, (int, float)) and math.isfinite(v)
                else "inf"
                for k, v in spec.compare_tols.items()}
        return rec

    def result_table(self) -> str:
        flat = [r.flat() for r in self.records]
        return table(flat, self.spec.result_columns)
