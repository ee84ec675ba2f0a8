"""Counters the program records as kwargs of its own spans, summed over a
traced run: the ones that ``spans.records`` does not keep (the hybrid
serve engine's ``carried`` and ``moe_rows`` on ``serve.prefill`` and
``serve.decode``, and ``moe_local`` and ``moe_local_prefill`` on
``serve.emit``). Read from the profile of the traced run in progress
through ``spans.host_events``; a program that records no such kwarg
gives None, never an error."""
from __future__ import annotations

import functools
import glob

from perfbench import spans


@functools.lru_cache(maxsize=1)
def _events(path: str) -> list:
    return spans.host_events(path)


def events() -> list:
    """(name, start_ns, end_ns, kwargs) of the program's spans in the
    traced run in progress (none where there is no profile)."""
    from perfbench import run
    found = glob.glob(str(run.TRACE_DIR / "**" / "*.xplane.pb"),
                      recursive=True)
    return _events(found[0]) if found else []


def total(evs: list, names: tuple, key: str):
    """Sum of ``key`` over the spans named in ``names`` that carry it;
    None where none does."""
    vals = [kw[key] for n, _s, _e, kw in evs if n in names and key in kw]
    return sum(vals) if vals else None
