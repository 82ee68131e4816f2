"""What a measured window saw, and the arithmetic over it.

The harness stamps every query it offers with the time it was due (the
window's clock, ``perf_counter_ns``), the time it was submitted and the
time it resolved.  In a closed loop a query is due when it is submitted.
In an open loop it is due at its arrival time, whether or not the planner
has room for it, so the latency counts the wait for admission too.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence


@dataclasses.dataclass
class Offer:
    """One query offered to the planner: its stamps (ns) and its ticket."""
    tables: tuple
    due_ns: int
    submit_ns: int = 0
    ticket: object = None      # the service's QueryTicket

    @property
    def resolve_ns(self) -> Optional[int]:
        return None if self.ticket is None else self.ticket.resolve_ns

    @property
    def latency_s(self) -> Optional[float]:
        r = self.resolve_ns
        return None if r is None else (r - self.due_ns) / 1e9


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Linearly interpolated percentile ``p`` (0-100) of ``values``."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return float(xs[lo] + (k - lo) * (xs[hi] - xs[lo]))


@dataclasses.dataclass
class Window:
    """The measured window: its bounds (ns), the queries it counts and
    the service waves it ran.  ``counted`` are the queries resolved inside
    the window (closed loop) or due inside it (open loop)."""
    start_ns: int
    end_ns: int
    counted: List[Offer]
    waves: int
    open_loop: bool
    setup_s: float

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def resolved(self) -> List[Offer]:
        return [o for o in self.counted if o.resolve_ns is not None]

    def latencies(self) -> List[float]:
        return [o.latency_s for o in self.resolved]

    def admit_waits_ms(self) -> List[float]:
        return [(o.submit_ns - o.due_ns) / 1e6 for o in self.counted]


def resolved_within(offers: Sequence[Offer], start_ns: int,
                    end_ns: int) -> List[Offer]:
    """Offers whose plan resolved inside [start_ns, end_ns)."""
    return [o for o in offers if o.resolve_ns is not None
            and start_ns <= o.resolve_ns < end_ns]
