"""The readers of the program's sweep, launch and compile counts, each on
a hand-built context."""
import pytest

import repro.obs
from bench import spec
from bench.run_cell import Context
from bench.window import Window

NEW = ("broker.sync_sweep_pct", "backend.launches_per_wave",
       "backend.launch_ms_per_wave", "backend.compiles")


def _ctx(waves=4, spans=()):
    window = Window(start_ns=0, end_ns=10**9, counted=[], waves=waves,
                    open_loop=False, setup_s=0.0)
    return Context(window=window, obs_spans=list(spans))


def _span(name, dur_us):
    return {"name": name, "ts": 0.0, "dur": dur_us, "tid": 1,
            "args": {"depth": 0}}


@pytest.fixture
def counts():
    m = repro.obs.get_metrics()
    m.reset()
    yield m
    m.reset()


@pytest.mark.parametrize("suffix", ["", ".grid10m"])
def test_sync_sweep_share(counts, suffix):
    read = spec.reader("broker.sync_sweep_pct" + suffix)
    assert read(_ctx()) is None                 # nothing swept
    counts.counter("broker.sweep_rows.async").inc(10)
    assert read(_ctx()) == 0.0
    counts.counter("broker.sweep_rows.sync").inc(30)
    assert read(_ctx()) == pytest.approx(75.0)


@pytest.mark.parametrize("suffix", ["", ".grid10m"])
def test_launches_per_wave(counts, suffix):
    read = spec.reader("backend.launches_per_wave" + suffix)
    assert read(_ctx()) == 0.0
    counts.counter("backend.launches").inc(12)
    assert read(_ctx(waves=4)) == pytest.approx(3.0)
    assert read(_ctx(waves=0)) is None


@pytest.mark.parametrize("suffix", ["", ".grid10m"])
def test_launch_ms_per_wave(counts, suffix):
    read = spec.reader("backend.launch_ms_per_wave" + suffix)
    spans = [_span("backend.launch", 1500.0), _span("backend.launch", 500.0),
             _span("broker.dispatch", 9000.0)]
    assert read(_ctx(waves=4, spans=spans)) == pytest.approx(0.5)
    assert read(_ctx(spans=())) == 0.0
    assert read(Context(window=_ctx().window, obs_spans=None)) is None


@pytest.mark.parametrize("suffix", ["", ".grid10m"])
def test_compiles(counts, suffix):
    read = spec.reader("backend.compiles" + suffix)
    assert read(_ctx()) == 0
    counts.counter("backend.compiles").inc(2)
    assert read(_ctx()) == 2


def test_a_program_without_the_counters_reads_nothing(counts, monkeypatch):
    """The parent of the program that added these counters keeps none of
    them: every reader gives None, and the result line leaves it out."""
    counts.counter("backend.launches").inc(5)
    monkeypatch.delattr(repro.obs, "ALWAYS_ON")
    spans = [_span("backend.launch", 100.0)]
    for name in NEW:
        assert spec.reader(name)(_ctx(spans=spans)) is None, name
