"""Window accounting: due-time latency, drained arrivals, percentiles."""
import pytest

from bench.run_cell import Loop
from bench.traffic.generator import Query
from bench.window import Offer, Window, percentile, resolved_within


class _Ticket:
    def __init__(self, resolve_ns=None, joint=None):
        self.resolve_ns = resolve_ns
        self.joint = joint


def test_percentile_interpolates_linearly():
    xs = [float(i) for i in range(1, 101)]
    assert percentile(xs, 50) == pytest.approx(50.5)
    assert percentile(xs, 95) == pytest.approx(95.05)
    assert percentile([3.0], 95) == 3.0
    assert percentile([], 50) is None


def test_latency_runs_from_due_time_not_submit():
    o = Offer(("t1", "t2"), due_ns=1_000, submit_ns=5_000,
              ticket=_Ticket(resolve_ns=11_000))
    assert o.latency_s == pytest.approx(10_000 / 1e9)
    w = Window(0, 20_000, [o], waves=1, open_loop=True, setup_s=1.0)
    assert w.admit_waits_ms() == [pytest.approx(4_000 / 1e6)]


def test_resolved_within_counts_only_the_window():
    offers = [Offer((), 0, 0, _Ticket(r)) for r in (5, 10, 19, 20, None)]
    assert [o.resolve_ns for o in resolved_within(offers, 10, 20)] == [10, 19]


class _Service:
    """Resolves each query ``lag`` waves after it was submitted; each
    wave takes ``wave_ns`` of (fake) time."""

    def __init__(self, clock, lag, wave_ns):
        self.clock, self.lag, self.wave_ns = clock, lag, wave_ns
        self.inflight = []

    @property
    def active(self):
        return len(self.inflight)

    def submit(self, tables, tenant):
        t = _Ticket()
        self.inflight.append([t, self.lag])
        return t

    def step(self):
        self.clock[0] += self.wave_ns
        for e in self.inflight:
            e[1] -= 1
            if e[1] == 0:
                e[0].resolve_ns = self.clock[0]
        self.inflight = [e for e in self.inflight if e[1] > 0]


def test_open_loop_drains_arrivals_due_in_the_window(monkeypatch):
    clock = [0]
    monkeypatch.setattr("bench.run_cell.time.perf_counter_ns",
                        lambda: clock[0])
    monkeypatch.setattr("bench.run_cell.time.sleep",
                        lambda s: clock.__setitem__(0, clock[0] + 100))
    svc = _Service(clock, lag=3, wave_ns=400)

    class Dep:
        service = svc
    loop = Loop(Dep(), None)
    # due at 0, 500, 900, 1500 ns; the window is [0, 1000)
    arrivals = [Query(t / 1e9, 0, ("a", "b")) for t in (0, 500, 900, 1500)]
    offers = loop.open(arrivals, 0, 1000)
    assert [o.due_ns for o in offers] == [0, 500, 900]
    assert all(o.resolve_ns is not None for o in offers)
    lat = [o.latency_s * 1e9 for o in offers]
    # submitted at 0 -> waves end 400, 800, 1200: resolved at 1200.
    # 500 is offered at the boundary after wave 2 (800), 900 after wave 3
    # (1200); both resolve three waves after their submit.
    assert lat == pytest.approx([1200, 2000 - 500, 2400 - 900])
    assert svc.active == 0
