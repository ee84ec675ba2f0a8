"""Reproducibility manifest — CARAML's automation records exactly what ran."""
from __future__ import annotations

import functools
import json
import os
import pathlib
import platform
import subprocess
import sys
import time


@functools.lru_cache(maxsize=1)
def git_sha() -> str | None:
    """Commit of the tree being benchmarked, or None outside a checkout.

    Stamped into every manifest and ResultRecord so cross-run comparison
    can always answer *which code* produced each side of a delta.
    """
    try:
        proc = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=5, cwd=pathlib.Path(__file__).resolve().parent)
    except (OSError, subprocess.SubprocessError):
        return None
    sha = proc.stdout.strip()
    return sha if proc.returncode == 0 and sha else None


def build_manifest(extra: dict | None = None) -> dict:
    import jax

    from repro.core import device
    m = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "git_sha": git_sha(),
        "python": sys.version.split()[0],
        "jax": jax.__version__,
        **device.current(),       # platform, device_kind, device_count
        "host": platform.platform(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "argv": sys.argv,
    }
    if extra:
        m.update(extra)
    return m


def write_manifest(out_dir, extra: dict | None = None) -> dict:
    m = build_manifest(extra)
    p = pathlib.Path(out_dir)
    p.mkdir(parents=True, exist_ok=True)
    (p / "manifest.json").write_text(json.dumps(m, indent=1, default=str))
    return m
