"""Span arithmetic for the per-layer metric readers.

A span is an event of the program's span tracer (``repro.obs``): a dict
with ``name``, ``ts`` and ``dur`` in microseconds, ``tid`` and
``args["depth"]``, the number of spans open around it on its thread.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Iterable, List

from bench.trace_reduce import union_ns


def named(spans: Iterable[dict], names) -> List[dict]:
    return [s for s in spans if s["name"] in names]


def total_ms(spans: Iterable[dict], names) -> float:
    return sum(s["dur"] for s in named(spans, names)) / 1e3


def self_ms(spans: List[dict], names) -> float:
    """Time of the ``names`` spans less the time in which any deeper span
    on the same thread ran inside them.  Nested spans are merged first:
    a span emitted on close (``complete``) takes the depth it closes at,
    so it can enclose a sibling of the same depth."""
    by_tid = defaultdict(list)
    for s in spans:
        by_tid[s["tid"]].append(s)
    for evs in by_tid.values():
        evs.sort(key=lambda s: s["ts"])
    starts = {t: [s["ts"] for s in evs] for t, evs in by_tid.items()}
    total = 0.0
    for p in named(spans, names):
        a, b = p["ts"], p["ts"] + p["dur"]
        d = p["args"].get("depth", 0)
        evs, ts = by_tid[p["tid"]], starts[p["tid"]]
        inner = [(c["ts"], c["ts"] + c["dur"]) for c in
                 evs[bisect.bisect_left(ts, a):bisect.bisect_right(ts, b)]
                 if c is not p and c["args"].get("depth", 0) > d
                 and c["ts"] + c["dur"] <= b]
        total += p["dur"] - sum(y - x for x, y in union_ns(inner))
    return total / 1e3

