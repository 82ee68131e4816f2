"""trace_reduce on a small recorded trace, checked by hand."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce

TRACE = Path(__file__).parent / "data" / "small_trace.json"


@pytest.fixture(scope="module")
def small():
    return json.loads(TRACE.read_text())


def test_union_merges_overlaps_and_drops_empty():
    assert trace_reduce.union_ns([(5, 9), (0, 3), (2, 4), (9, 12),
                                  (20, 20)]) == [(0, 4), (5, 12)]


def test_small_trace_by_hand(small):
    devices = {k: [tuple(e) for e in v] for k, v in small["devices"].items()}
    host = [tuple(h) for h in small["host"]]
    got = trace_reduce.reduce(devices, tuple(small["window"]), host)
    # window [1000, 2000): ops cover [1000, 1200) (clipped from 900),
    # [1300, 1500) and [1400, 1600) -> [1300, 1600), and [1900, 2000)
    # (clipped from 2100): busy 200 + 300 + 100 = 600 ns
    assert got["busy_s"] == pytest.approx(600e-9)
    assert got["window_s"] == pytest.approx(1000e-9)
    # gaps: [1200, 1300) mid 1250 inside "lockstep.queue" (depth 1,
    # inside "bench.step"); [1600, 1900) mid 1750 inside only
    # "bench.step"
    assert got["idle_gaps"] == [["bench.step", pytest.approx(300e-9)],
                                ["lockstep.queue", pytest.approx(100e-9)]]
    # per op: jit_scan/fusion.1 200 + 200 = 400 ns, jit_scan/copy.2
    # 200 + 100 (clipped) = 300 ns
    ops = dict((k, v) for k, v in got["device_ops"])
    assert ops == {"jit_scan/fusion.1": pytest.approx(400e-9),
                   "jit_scan/copy.2": pytest.approx(300e-9)}
    assert [k for k, _ in got["device_ops"]] == ["jit_scan/fusion.1",
                                                 "jit_scan/copy.2"]


def test_two_devices_average(small):
    dev = [tuple(e) for e in small["devices"]["/device:TPU:0"]]
    got = trace_reduce.reduce({"a": dev, "b": [("x", 1000, 1500)]},
                              tuple(small["window"]), [])
    assert got["busy_s"] == pytest.approx((600e-9 + 500e-9) / 2)
    assert got["idle_gaps"][0][0] == "no span"


def test_nothing_on_the_device_reads_nothing():
    assert trace_reduce.reduce({}, (0, 10), []) is None
    assert trace_reduce.reduce({"a": [("x", 20, 30)]}, (0, 10), []) is None
