"""Span arithmetic of the per-layer readers, checked by hand."""
import pytest

from bench import spans


def _span(name, ts, dur, depth, tid=1):
    return {"name": name, "ts": ts, "dur": dur, "tid": tid,
            "args": {"depth": depth}}


def test_self_time_subtracts_the_union_of_what_runs_inside():
    evs = [_span("lockstep.queue", 0, 100, 0),
           # emitted on close at depth 1, enclosing a depth-1 sibling
           _span("broker.wave", 10, 50, 1),
           _span("broker.dispatch.group", 20, 20, 1),
           _span("broker.group.sync", 25, 10, 2),
           _span("broker.wave.commit", 70, 10, 1),
           _span("other.thread", 0, 100, 1, tid=2)]
    assert spans.self_ms(evs, ("lockstep.queue",)) == pytest.approx(
        (100 - 50 - 10) / 1e3)
    assert spans.total_ms(evs, ("broker.wave.commit",)) == \
        pytest.approx(0.01)
