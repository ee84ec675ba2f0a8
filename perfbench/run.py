"""One run of one benchmark cell.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names a configuration (``perfbench/configs/<config>.json``) and a traffic
mix (``perfbench/mixes/<traffic>.json``); the configuration names its
plain reference (``perfbench/reference/<reference>.py``, ``model`` where
it names none); the mix names its driver
(``perfbench/drivers/<driver>.py``); the cell's comparison limits are in
``perfbench/limits/<cell>.json``; each per-layer metric is read by
``perfbench/layers/<metric>.py``.

The run loads, warms up, measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON line last
on standard output. ``--trace 1`` profiles the window and reports the
cell's per-layer metrics instead of its end-to-end ones. A run that finds
no TPU, or fewer chips than the cell asks for, exits 2 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "perfbench"
#: JAX's persistent compilation cache: the directory that
#: ``JAX_COMPILATION_CACHE_DIR`` names, else a fixed path inside the checkout
CACHE_DIR = pathlib.Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                         or ROOT / ".jax_cache")
#: where a traced run's profile is written, read and deleted
TRACE_DIR = ROOT / ".perfbench_trace"


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(bench: dict, workload: str) -> dict:
    """The cell's entry and the data files it names."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[cell["config"]]
    return {"cell": cell,
            "config": read_json(ROOT / conf["file"]),
            "mix": read_json(HERE / "mixes" / f"{cell['traffic']}.json"),
            "limits": read_json(HERE / "limits" / f"{workload}.json")}


def cell_metrics(bench: dict, workload: str) -> tuple[list, list]:
    """The cell's end-to-end metrics, and its per-layer metrics."""
    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layers = [m for m in bench["per_layer"]
              if mine(m) and m["moves"] in names]
    return e2e, layers


def setup_jax() -> None:
    """The compile cache (``CACHE_DIR``), for every program."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"JAX found platform {info['platform']!r} "
                     f"({info['kind']}), not a TPU")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{info['count']}")
    return info


class Context:
    """What a driver gets: the cell's data, the run's arguments, and the
    harness services (compile counting, spans, memory, tracing)."""

    def __init__(self, files: dict, seed: int, seconds: float, trace: bool,
                 chips: int):
        self.cell = files["cell"]
        self.cfg, self.mix, self.limits = (files["config"], files["mix"],
                                           files["limits"])
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.chips = chips
        self.t_start = T_START
        self.window_start = None
        self.compiles_in_window = 0
        self._in_window = False
        self.memory_peak_bytes = None
        self.trace_path = None
        self.trace_window_s = None
        self.notes: dict = {}
        import jax
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_kw) -> None:
        if self._in_window and event == \
                "/jax/core/compile/backend_compile_duration":
            self.compiles_in_window += 1

    def span(self, name: str):
        return self._jax.profiler.TraceAnnotation(name)

    def open_window(self) -> float:
        self.window_start = time.perf_counter()
        self._in_window = True
        return self.window_start

    def close_window(self) -> float:
        self._in_window = False
        return time.perf_counter()

    def read_memory(self, devices=None) -> int:
        """Peak bytes in use on the fullest chip (the process's peak)."""
        devs = devices or self._jax.devices()[:self.chips]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None
        return self.memory_peak_bytes

    def start_trace(self) -> None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        self._jax.profiler.start_trace(str(TRACE_DIR))
        self._t_trace = time.perf_counter()

    def stop_trace(self) -> None:
        # the window ends here: stopping the profiler writes the trace
        self.trace_window_s = time.perf_counter() - self._t_trace
        self._jax.profiler.stop_trace()
        found = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"),
                          recursive=True)
        self.trace_path = found[0] if found else None


def judge(checks: list, failed: int = 0) -> bool:
    """The rule that decides ``correct``: no request failed, and every
    number compared is within its limit. ``checks`` holds (name, value,
    limit) triples."""
    return failed == 0 and all(v <= lim for _n, v, lim in checks)


def compared(checks: list) -> dict:
    """The numbers compared as the result line gives them."""
    return {n: {"value": v, "limit": lim} for n, v, lim in checks}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             check_chip=device_info, prepare=None) -> dict:
    """Run one cell and return its result line (a dict). ``check_chip``
    is the look for a chip; ``prepare(files, driver)`` lets a test shrink
    the cell or plant a fault underneath before the run."""
    bench = read_json(ROOT / "BENCHMARK.json")
    files = cell_files(bench, workload)
    e2e, layer_metrics = cell_metrics(bench, workload)
    setup_jax()
    device = check_chip(files["cell"]["chips"])
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    driver = load_module(HERE / "drivers" / f"{files['mix']['driver']}.py")
    if prepare is not None:
        prepare(files, driver)
    ctx = Context(files, seed, seconds, trace, files["cell"]["chips"])
    out = driver.run(ctx)
    log(f"[run] compiles_in_window={ctx.compiles_in_window} "
        f"memory_peak_bytes={ctx.memory_peak_bytes} notes={ctx.notes}")
    checks = out["checks"]
    correct = judge(checks, out["failed"])
    metrics: dict = {}
    breakdown = None
    if trace:
        from perfbench import trace as tr
        red = tr.reduce(tr.load(ctx.trace_path), ctx.chips)
        facts = dict(out["facts"], trace=red, chips=ctx.chips,
                     window_s=ctx.trace_window_s, device=device)
        for m in layer_metrics:
            reader = load_module(HERE / "layers" / f"{m['name']}.py")
            v = reader.read(facts, ctx.cfg, ctx.mix)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = dict(device, busy_s=red["busy_s"],
                      window_s=ctx.trace_window_s)
        breakdown = {"device_ops": red["top_ops"][:10],
                     "idle_gaps": red["top_gaps"][:10]}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=ctx.memory_peak_bytes)
    line = {"correct": bool(correct), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared(checks)
    for n, v, lim in checks:
        log(f"[check] {n} = {v!r} (limit {lim!r}) "
            f"{'ok' if v <= lim else 'FAIL'}")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoChip as e:
        log(f"error: {e}")
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
