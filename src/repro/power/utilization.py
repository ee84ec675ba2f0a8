"""Utilization sources for the analytic TPU power model.

``TPUModelPower`` converts a utilization fraction into watts
(``P = idle + (TDP - idle) * u``); this module supplies that fraction
from the roofline occupancy of the compiled steps (the dry-run
artifacts under ``artifacts/dryrun/``). Where none exist it raises
rather than bill a constant.
"""
from __future__ import annotations

import json
import os
import pathlib
from typing import Callable, Optional


def _dryrun_dir(override: Optional[str] = None) -> pathlib.Path:
    d = override or os.environ.get("REPRO_DRYRUN_DIR")
    if d:
        return pathlib.Path(d)
    # anchored to the repo root, not the cwd (same convention as the
    # roofline workload)
    repo_root = pathlib.Path(__file__).resolve().parents[3]
    return repo_root / "artifacts" / "dryrun"


def roofline_fractions(dryrun_dir=None) -> list[float]:
    """All finite ``roofline_fraction`` values in the dry-run artifacts
    (empty when the directory or the field is absent)."""
    out = []
    d = _dryrun_dir(dryrun_dir)
    if not d.is_dir():
        return out
    for f in sorted(d.glob("*.json")):
        try:
            r = json.loads(f.read_text())
            frac = float(r["roofline"]["roofline_fraction"])
        except (OSError, ValueError, TypeError, KeyError,
                json.JSONDecodeError):
            continue
        if 0.0 <= frac:
            out.append(min(frac, 1.0))
    return out


def roofline_utilization_fn(dryrun_dir=None) -> Callable[[], float]:
    """A ``TPUModelPower.utilization_fn`` backed by roofline occupancy.

    Averages the ``roofline_fraction`` across the dry-run artifacts —
    the occupancy of the compiled steps this host would run. Raises
    FileNotFoundError where no roofline data exists: billing a constant
    instead would pass a guess off as the device's modeled energy.
    """
    fracs = roofline_fractions(dryrun_dir)
    if not fracs:
        raise FileNotFoundError(
            f"tpu_model power needs a utilization source: no roofline "
            f"dry-run artifacts under {_dryrun_dir(dryrun_dir)} — run "
            f"`python -m repro.launch.dryrun`, or measure with --power none")
    u = sum(fracs) / len(fracs)
    return lambda: u
