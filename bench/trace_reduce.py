"""Reduces a profiler trace of the window to device numbers.

``from_profile_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote,
keeps the op events of every TPU device plane, and puts the host spans
(the program's span tracer and the harness's own, on ``perf_counter_ns``)
onto the trace's clock through one marker annotation whose
``perf_counter_ns`` the harness stamped.  ``reduce`` then gives, over the
window:

- ``busy_s``: the union of the intervals in which an op ran on a device,
  averaged over the devices; ``window_s``: the window's length;
- ``device_ops``: the ten ops (by program and op name) that took most
  device time, in seconds averaged over the devices;
- ``idle_gaps``: the device's idle time split by the innermost host span
  open at each gap's middle, the ten largest, in seconds;
- ``ops_in_window``: the device op events that overlap the window;
- ``host``: the host spans on the trace's clock.

``reduce`` works on plain lists, so ``bench/tests`` checks it by hand on
a small recorded trace.
"""
from __future__ import annotations

import glob
import heapq
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)
HostSpan = Tuple[str, int, int, int]     # (name, start_ns, end_ns, depth)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def union_ns(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted, disjoint intervals covering ``intervals``."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: int, b: int, lo: int, hi: int) -> Tuple[int, int]:
    return max(a, lo), min(b, hi)


def reduce(devices: Dict[str, List[Interval]], window: Tuple[int, int],
           host: Sequence[HostSpan]) -> Optional[dict]:
    """Device busy, top ops and idle gaps over ``window`` (module
    docstring); None when no device ran anything in it."""
    lo, hi = window
    if not devices or hi <= lo:
        return None
    busy, per_op = [], defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    n_ops = 0
    for dev in sorted(devices):
        ivs = []
        for name, a, b in devices[dev]:
            a, b = _clip(a, b, lo, hi)
            if b > a:
                ivs.append((a, b))
                per_op[name] += (b - a) / 1e9 / len(devices)
        n_ops += len(ivs)
        merged = union_ns(ivs)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        names = _open_at(host, [(a + b) // 2 for a, b in idle])
        for (a, b), name in zip(idle, names):
            gaps[name] += (b - a) / 1e9 / len(devices)
    if not any(busy):
        return None
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle],
            "ops_in_window": n_ops,
            "host": list(host)}


def _open_at(host: Sequence[HostSpan], ts: Sequence[int]) -> List[str]:
    """The innermost host span open at each of ``ts`` ("no span" where
    none; of equally deep spans the first listed), in one sweep."""
    order = sorted(range(len(host)), key=lambda i: host[i][1])
    named: Dict[int, str] = {}
    heap: List[Tuple[int, int]] = []          # (-depth, index): open spans
    j = 0
    for t in sorted(set(ts)):
        while j < len(order) and host[order[j]][1] <= t:
            i = order[j]
            heapq.heappush(heap, (-host[i][3], i))
            j += 1
        while heap and host[heap[0][1]][2] <= t:   # ended: for every later t
            heapq.heappop(heap)
        named[t] = host[heap[0][1]][0] if heap else "no span"
    return [named[t] for t in ts]


# ------------------------------ xplane ------------------------------------ #

def _xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def device_ops(pd) -> Dict[str, List[Interval]]:
    """Op events of each TPU device plane, named ``<program>/<op>`` where
    the program (module) line encloses the op."""
    out: Dict[str, List[Interval]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        mods = sorted((int(e.start_ns), int(e.end_ns), _program(e.name))
                      for e in lines[MODULES_LINE].events) \
            if MODULES_LINE in lines else []
        ops = sorted((int(e.start_ns), int(e.end_ns), e.name)
                     for e in lines[OPS_LINE].events)
        names: Dict[Tuple[str, str], str] = {}
        evs, k = [], 0
        for a, b, op in ops:
            while k < len(mods) and mods[k][1] <= a:
                k += 1
            prog = mods[k][2] if k < len(mods) and mods[k][0] <= a else "?"
            name = names.get((prog, op))
            if name is None:
                name = names[(prog, op)] = f"{prog}/{op}"
            evs.append((name, a, b))
        out[plane.name] = evs
    return out


def _program(module: str) -> str:
    """A module event's program name without its run id: ``jit_f(12)``
    -> ``jit_f``."""
    return module.split("(")[0]


def marker_ns(pd, name: str) -> Optional[int]:
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    return int(e.start_ns)
    return None


def from_profile_dir(log_dir: str, marker: str, marker_perf_ns: int,
                     window_perf: Tuple[int, int],
                     host_perf: Sequence[HostSpan]) -> Optional[dict]:
    """Load the trace under ``log_dir`` and ``reduce`` it over the window
    given on the ``perf_counter_ns`` clock; None without a device plane
    or without the marker."""
    path = _xplane(log_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    m = marker_ns(pd, marker)
    devs = device_ops(pd)
    if m is None or not devs:
        print(f"trace_reduce: marker {'found' if m is not None else 'missing'}"
              f", device op lines {sorted(devs)}; planes "
              f"{[(p.name, [ln.name for ln in p.lines]) for p in pd.planes]}",
              file=sys.stderr)
        return None
    off = m - marker_perf_ns
    host = [(n, a + off, b + off, d) for n, a, b, d in host_perf]
    return reduce(devs, (window_perf[0] + off, window_perf[1] + off), host)
