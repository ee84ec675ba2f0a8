"""Compatibility shim: the benchmark driver is now `python -m repro.bench`.

The old per-benchmark subprocess loop is gone — one process runs every
workload through the WorkloadSpec registry, and multi-device workloads
get their CPU devices configured in-process.

  PYTHONPATH=src python -m repro.bench run              # everything
  PYTHONPATH=src python -m repro.bench run --tags smoke # CI smoke set
"""
from __future__ import annotations

import sys

from repro.bench.cli import main as bench_main


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    return bench_main(["run", *argv])


if __name__ == "__main__":
    sys.exit(main())
