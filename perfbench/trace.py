"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

- busy time: the union of the intervals in which an operation ran on a
  device (the device plane's "XLA Ops" line), averaged over the chips used;
- time by operation and by program: summed durations of the "XLA Ops"
  line per ``program:op`` (the op's kind, put down to the XLA module
  running it; a Pallas kernel is a ``custom-call``) and of the "XLA
  Modules" line per program, averaged over the chips used;
- idle gaps: the stretches of the traced window on the first chip with
  no operation running, each put down to the innermost benchmark span
  (host events whose name starts with the span prefix) covering its
  middle, and summed per span name. The profiler's device clock runs
  about 1.4 ms behind the host's (measured on a v5e), so gaps shorter than
  that are put down loosely.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: ops whose event spans the ops of their body: counted in busy time, not
#: in the time by op
CONTAINER = re.compile(r":(while|conditional|call)$")


def load(path: str) -> list:
    """Planes as plain data: [{"name", "lines": [{"name", "events":
    [(name, start_ns, dur_ns)]}]}]."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [(e.name, float(e.start_ns),
                                      float(e.duration_ns))
                                     for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals) -> list:
    """Merged [start, end) intervals, sorted."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def device_planes(planes: list, chips: int) -> list:
    devs = [(int(m.group(1)), p) for p in planes
            if (m := DEVICE_PLANE.match(p["name"]))]
    return [p for _, p in sorted(devs, key=lambda t: t[0])[:chips]]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_spans(planes: list, prefix: str) -> list:
    """(name, start_ns, end_ns) of every host event named with ``prefix``."""
    out = []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for line in p["lines"]:
            out += [(n, s, s + d) for n, s, d in line["events"]
                    if n.startswith(prefix)]
    return out


def attribute(gaps: list, spans: list) -> dict:
    """Seconds of idle per innermost covering span name."""
    by: dict = defaultdict(float)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [(se - ss, n) for n, ss, se in spans if ss <= mid < se]
        name = min(cover)[1] if cover else "(no benchmark span)"
        by[name] += (e - s) * 1e-9
    return dict(by)


def short(name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``; ``%fusion.12 = bf16[..] ...`` ->
    ``fusion``; a custom call (a Pallas kernel) -> ``custom-call``."""
    if " custom-call(" in name:
        return "custom-call"
    head = name.split(" = ")[0].lstrip("%")
    return re.sub(r"(\.\d+)+$|\(\d+\)$", "", head)


def _keyed_ops(plane: dict) -> list:
    """The plane's operations as (program:op, start, duration): each op is
    put down to the program (XLA module) running when it started."""
    mods = sorted((s, s + d, short(n))
                  for n, s, d in _line(plane, MODULES_LINE))
    starts = [m[0] for m in mods]
    out = []
    for n, s, d in _line(plane, OPS_LINE):
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and s < mods[i][1] else "(no program)"
        out.append((f"{prog}:{short(n)}", s, d))
    return out


def reduce(planes: list, chips: int, prefix: str = "bench.") -> dict:
    devs = device_planes(planes, chips)
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    ops: dict = defaultdict(float)
    mods: dict = defaultdict(float)
    busy = 0.0
    per_device = []
    for p in devs:
        evs = _keyed_ops(p)
        per_device.append(evs)
        for n, _s, d in evs:
            if not CONTAINER.search(n):
                ops[n] += d * 1e-9 / len(devs)
        for n, _s, d in _line(p, MODULES_LINE):
            mods[short(n)] += d * 1e-9 / len(devs)
        busy += sum(e - s for s, e in union(
            (s, s + d) for _n, s, d in evs)) * 1e-9 / len(devs)
    first = union((s, s + d) for _n, s, d in per_device[0])
    spans = host_spans(planes, prefix)
    window = [s for s in spans if s[0] == prefix + "window"]
    lo = min((s[1] for s in window), default=first[0][0] if first else 0.0)
    hi = max((s[2] for s in window), default=first[-1][1] if first else 0.0)
    gaps, t = [], lo
    for s, e in first:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps = [(s, e) for s, e in gaps if e > s]
    by_span = attribute(gaps, spans)
    top = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                           key=lambda kv: -kv[1])
    return {"busy_s": busy, "ops": dict(ops), "modules": dict(mods),
            "top_ops": top(ops), "top_gaps": top(by_span),
            "n_gaps": len(gaps), "device_ops": per_device}


def matching(times: dict, pattern: str) -> float:
    """Summed seconds of every name that ``pattern`` (a regex) finds."""
    rx = re.compile(pattern)
    return sum(v for k, v in times.items() if rx.search(k))


def exposed(red: dict, pattern: str) -> float:
    """Seconds, averaged over the chips, in which an operation that
    ``pattern`` finds ran and no other operation did."""
    rx = re.compile(pattern)
    total = 0.0
    for evs in red["device_ops"]:
        mine = union((s, s + d) for n, s, d in evs if rx.search(n))
        other = union((s, s + d) for n, s, d in evs
                      if not rx.search(n) and not CONTAINER.search(n))
        j = 0
        for s, e in mine:
            cover = 0.0
            while j < len(other) and other[j][1] <= s:
                j += 1
            k = j
            while k < len(other) and other[k][0] < e:
                cover += min(e, other[k][1]) - max(s, other[k][0])
                k += 1
            total += (e - s) - cover
    return total * 1e-9 / max(len(red["device_ops"]), 1)
