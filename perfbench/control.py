"""Readings that set a cell's limits: the program's on many seeds, and the
control's (the reference at the next precision down in the program's
place) and the planted faults' on some of them, all at the cell's own
size and in one process.

    python perfbench/control.py --workload <cell> --seeds 12 --control 3 \
        --seconds 15 [--out chiprun_out/control.jsonl]

Each seed runs the cell as a run does, with a window of ``--seconds``
(training: one step after the checked ones), and the same comparison;
the first ``--control`` seeds then also read the control and the faults.
Each reading is judged against the cell's limits by the rule that decides
a run's ``correct`` (``run.judge``): a control or a fault has to come out
``"correct": false``. One JSON line per seed. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from perfbench import run as R  # noqa: E402


def verdict(checks: list, failed: int = 0) -> dict:
    return {"correct": R.judge(checks, failed), "failed": failed,
            "compared": R.compared(checks)}


def controls(driver, ctx, out) -> dict:
    """Each control's and fault's numbers and verdict, by name."""
    return {k: verdict(checks) for k, checks in driver.control(ctx, out).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--first-seed", type=int, default=7_000_000_001)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = R.read_json(R.ROOT / "BENCHMARK.json")
    files = R.cell_files(bench, args.workload)
    R.setup_jax()
    R.device_info(files["cell"]["chips"])
    sys.path.insert(0, str(R.ROOT / "src"))
    driver = R.load_module(R.HERE / "drivers" / f"{files['mix']['driver']}.py")
    sink = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ctx = R.Context(files, seed, args.seconds, False,
                        files["cell"]["chips"])
        t = time.perf_counter()
        out = driver.run(ctx)
        row = {"workload": args.workload, "seed": seed,
               "program": verdict(out["checks"], out["failed"]),
               "attempted": out["attempted"],
               "notes": {k: v for k, v in ctx.notes.items()
                         if isinstance(v, (int, float))}}
        if i < args.control:
            row.update(controls(driver, ctx, out))
        row["seconds"] = time.perf_counter() - t
        del out
        line = json.dumps(row)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
