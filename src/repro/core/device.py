"""The device a run executes on, the published figures of each chip kind,
and the persistent compilation cache the entry points share.

Every figure a power model or roofline applies to a device comes from
:data:`CHIPS`, keyed by ``device_kind`` as JAX reports it. A kind that
is not in the table is an error, never a default: a v5e number applied
to another chip would mislabel every derived metric.
"""
from __future__ import annotations

import os
import pathlib
from dataclasses import dataclass

#: repository root (``src/repro/core/device.py`` -> three levels up)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
#: the compile cache's fixed home when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a path that never moves, so a later run finds what an earlier
#: one compiled (the path is part of the cache key)
COMPILE_CACHE_DIR = REPO_ROOT / ".jax_cache"


@dataclass(frozen=True)
class Chip:
    """Per-chip peaks and power envelope of one device kind."""

    peak_bf16_flops: float        # FLOP/s
    peak_int8_ops: float          # OP/s
    hbm_bytes: float
    hbm_bw: float                 # B/s
    ici_bw: float                 # B/s per link
    tdp_w: float
    idle_w: float
    source: str


CHIPS = {
    "TPU v5 lite": Chip(
        peak_bf16_flops=197e12, peak_int8_ops=393e12, hbm_bytes=16e9,
        hbm_bw=819e9,
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        ici_bw=50e9,
        # no published per-chip power: a modeled envelope, labeled so
        tdp_w=220.0, idle_w=60.0,
        source="Google Cloud documentation, 'TPU v5e' (peaks, HBM, ICI); "
               "tdp_w/idle_w are modeling assumptions, not published"),
}


def chip(kind: str) -> Chip:
    """The published figures of ``kind``; KeyError for an unknown kind."""
    try:
        return CHIPS[kind]
    except KeyError:
        raise KeyError(f"no published figures for device kind {kind!r}; "
                       f"known kinds: {sorted(CHIPS)}") from None


def current() -> dict:
    """``platform``, ``device_kind`` and ``device_count`` of this process's
    default backend, as JAX reports them."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`. Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
