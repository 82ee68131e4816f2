"""The program's always-on counters, as a per-layer reader sees them.

The harness resets the program's metrics registry when a traced window
opens, and reads the per-layer metrics once it has closed, with no
planning in between; so a counter's value at reading is its count over
the window.  A program that does not keep a counter (one older than the
counter) gives None, which leaves the metric out of the result line,
and a counter it keeps but never incremented in the window reads 0.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence


def window_counts(names: Sequence[str]) -> Optional[Dict[str, int]]:
    """``{name: count}`` for ``names`` over the window, or None where
    the program does not keep each of them always on."""
    import repro.obs as obs
    kept = getattr(obs, "ALWAYS_ON", ())
    if any(n not in kept for n in names):
        return None
    snap = obs.get_metrics().snapshot()
    return {n: int(snap.get(n, 0)) for n in names}
