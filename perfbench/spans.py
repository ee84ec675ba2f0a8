"""The program's own host spans in a traced run's profile: the serve
engine's (``serve.*``) and the train loop's (``train``, ``train.*``).

- ``engine_calls``: the serve engine's prefill and decode program calls
  in the traced run in progress, one dict per call, rebuilt from the
  kwargs of its ``serve.prefill`` and ``serve.decode`` spans. They carry
  the counters of the engine's ``StepRecord`` (``step``, ``iter``,
  ``rows``, ``width``, ``used``, ``n_steps``) and a prefill call's prefix
  depth ``npre``. The serve driver hands the readers its records without
  those counters, so the readers in ``layers/`` take them from the spans:
  they count what ran, not what the mix implies.
- ``idle``: ``trace.reduce``'s idle attribution with the program's spans
  beside the benchmark's: each idle gap of the traced window goes to the
  innermost benchmark (``bench.``) or program span over its middle.
  Without program spans it is ``reduce``'s own.

    python perfbench/spans.py <profile.xplane.pb> [--chips N]

prints both for a kept profile, as one JSON line.
"""
from __future__ import annotations

import argparse
import functools
import glob
import json
import pathlib
import sys

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from perfbench import trace as tr  # noqa: E402

#: the benchmark's span prefix, which ``trace.reduce`` attributes to
BENCH = "bench."
PROGRAM = ("serve.", "train.")
STEP_SPAN = "train"


def _program(name: str) -> bool:
    return name == STEP_SPAN or name.startswith(PROGRAM)


def host_events(path: str) -> list:
    """(name, start_ns, end_ns, kwargs) of every program span on the host
    planes of the profile at ``path``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if _program(e.name):
                    s = float(e.start_ns)
                    out.append((e.name, s, s + float(e.duration_ns),
                                dict(e.stats)))
    return out


def records(events: list) -> list:
    """The engine's program calls, in order, from its spans' kwargs. A
    decode span that dispatched nothing (every slot preempted) carries
    no ``k`` and is no call."""
    out = []
    for name, _s, _e, kw in events:
        if name == "serve.prefill":
            out.append({"kind": "prefill", "step": kw["step"],
                        "iter": kw["iter"], "rows": kw["rows"],
                        "width": kw["bucket"], "used": kw["used"],
                        "n_steps": 1, "npre": kw["npre"]})
        elif name == "serve.decode" and "k" in kw:
            out.append({"kind": "decode", "step": kw["step"],
                        "iter": kw["iter"], "rows": kw["rows"],
                        "width": kw["nb"], "used": kw["used"],
                        "n_steps": kw["k"]})
    return sorted(out, key=lambda r: r["step"])


@functools.lru_cache(maxsize=1)
def _profile_records(path: str) -> list:
    return records(host_events(path))


def engine_calls() -> list:
    """The engine's program calls in the traced run in progress (none
    where the program records no such span): ``run.py`` reads the
    per-layer metrics before it deletes the profile."""
    from perfbench import run
    found = glob.glob(str(run.TRACE_DIR / "**" / "*.xplane.pb"),
                      recursive=True)
    return _profile_records(found[0]) if found else []


def idle(planes: list, chips: int) -> dict:
    """Idle seconds of the traced window per innermost benchmark or
    program span, largest first: the program's spans take the benchmark's
    prefix for ``trace.reduce``'s walk and give it back after."""
    def named(p):
        if not p["name"].startswith("/host:"):
            return p
        return dict(p, lines=[
            dict(line, events=[(BENCH + n if _program(n) else n, s, d)
                               for n, s, d in line["events"]])
            for line in p["lines"]])

    def own(n):
        inner = n[len(BENCH):]
        return inner if n.startswith(BENCH) and _program(inner) else n

    red = tr.reduce([named(p) for p in planes], chips, BENCH)
    return {own(n): v for n, v in red["top_gaps"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("profile")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    out = {"idle_by_span": idle(tr.load(args.profile), args.chips),
           "engine_calls": len(records(host_events(args.profile)))}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
