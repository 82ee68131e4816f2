"""Device-sharded grid scans + double-buffered broker flushes.

Multi-device parity lanes run in SUBPROCESSES: ``XLA_FLAGS`` must be set
before the first jax import, and the main pytest process keeps the real
single CPU device (see tests/conftest.py).  The child re-derives every
probe surface from a seed, compares the sharded backend against its own
in-process float64 numpy oracle — random, ragged, tie-heavy, and
all-infeasible grids, on ``argmin_grid`` / ``argmin_grid_many`` /
``hill_climb_ensemble_many`` — and reports a JSON verdict on stdout.

In-process tests cover the single-device path of the sharded code (the
``REPRO_PLAN_DEVICES=1`` rollback switch), the ``_many_chunk`` dispatch
geometry, and the double-buffered broker: ``flush_async`` waves must be
bit-identical with sequential ``flush()`` — plans, resource-plan cache
contents, cache hit/miss counters, and broker request/batch stats — and
the pipelined Selinger / FastRandomized drivers must plan identically
through double-buffered, serial-flush, and legacy (no ``flush_async``)
brokers.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.cluster import paper_cluster
from repro.core.cost_model import simulator_cost_models
from repro.core.fast_randomized import fast_randomized_plan
from repro.core.plan_broker import PlanBroker
from repro.core.plan_cache import ResourcePlanCache
from repro.core.planning_backend import (MAX_LIVE_ELEMENTS, MIN_SHARD_ROWS,
                                         _many_chunk, _pad_even,
                                         _pad_multiple)
from repro.core.plans import OperatorCosting
from repro.core.schema import random_query, random_schema
from repro.core.selinger import selinger_plan

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")

SRC = str(Path(__file__).resolve().parents[1] / "src")


# --------------------- subprocess multi-device parity ----------------------- #
# The child compares the sharded backend against its own numpy oracle so
# grid construction lives in one place; the parent asserts the verdict.

_DRIVER = """
import json, math, sys
import numpy as np
import jax
import jax.numpy as jnp
from repro.core.cluster import ClusterConditions, ResourceDim
from repro.core.planning_backend import get_backend

name, want = sys.argv[1], int(sys.argv[2])
assert jax.device_count() == want, (jax.device_count(), want)
be = get_backend(name)
np_be = get_backend("numpy")
assert be.device_count() == want, (be.device_count(), want)


def table_fn(cluster, table, xp):
    ga, gb = (np.asarray(d.grid(), dtype=np.int64) for d in cluster.dims)
    t = xp.asarray(table)
    ga_x, gb_x = xp.asarray(ga), xp.asarray(gb)

    def fn(cfgs, params=None):
        a = xp.asarray(cfgs)
        return t[xp.searchsorted(ga_x, a[:, 0]),
                 xp.searchsorted(gb_x, a[:, 1])]
    return fn


def param_fn(xp):
    def fn(cfgs, params):
        a = xp.asarray(cfgs)
        return ((a[:, 0] * 37 + a[:, 1] * 11) % 101) * 8.0 + params[0]
    return fn


def cluster_of(kind, na, nb, rng):
    if kind == "ragged":
        step = int(rng.integers(2, 4))
        hi = 1 + step * (na - 1) + int(rng.integers(1, step))
        da = ResourceDim("a", 1, hi, step=step)
    else:
        da = ResourceDim("a", 0, na - 1)
    return ClusterConditions(dims=(da, ResourceDim("b", 0, nb - 1)))


def same(a, b):
    (ra, ca), (rb, cb) = a, b
    return ra == rb and (ca == cb or (math.isinf(ca) and math.isinf(cb)))


bad = []
for seed, kind, na, nb in [(0, "random", 9, 7), (1, "ragged", 12, 5),
                           (2, "ties", 13, 4), (3, "allinf", 6, 5),
                           (4, "random", 50, 1), (5, "ragged", 2, 2)]:
    rng = np.random.default_rng(seed)
    cluster = cluster_of(kind, na, nb, rng)
    shape = tuple(len(d.grid()) for d in cluster.dims)
    table = rng.integers(0, 1 << 20, size=shape).astype(np.float64)
    table[rng.random(shape) < 0.15] = np.inf
    if kind == "ties":
        table[rng.random(shape) < 0.6] = 7.0    # mass-tied minima
    if kind == "allinf":
        table[:] = np.inf
    # tiny chunk_size forces multiple sharded spans over the small grid
    got = be.argmin_grid(table_fn(cluster, table, jnp), cluster,
                         chunk_size=16)
    ref = np_be.argmin_grid(table_fn(cluster, table, np), cluster,
                            chunk_size=16)
    if not same(got, ref):
        bad.append([kind, "argmin_grid", repr(got), repr(ref)])
    pm = rng.integers(0, 1000, size=(5, 1)).astype(np.float64)
    gm = be.argmin_grid_many(param_fn(jnp), cluster, pm, chunk_size=8)
    rm = np_be.argmin_grid_many(param_fn(np), cluster, pm, chunk_size=8)
    if not all(same(g, r) for g, r in zip(gm, rm)):
        bad.append([kind, "argmin_grid_many", repr(gm), repr(rm)])
    gh = be.hill_climb_ensemble_many(param_fn(jnp), cluster, pm[:3],
                                     n_random=4, seed=seed)
    rh = np_be.hill_climb_ensemble_many(param_fn(np), cluster, pm[:3],
                                        n_random=4, seed=seed)
    if not all(same(g, r) for g, r in zip(gh, rh)):
        bad.append([kind, "climb_many", repr(gh), repr(rh)])
print(json.dumps({"devices": jax.device_count(), "ok": not bad,
                  "bad": bad}))
"""


def _run_sharded_lane(backend: str, devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("REPRO_PLAN_DEVICES", None)
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER, backend, str(devices)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


@needs_jax
@pytest.mark.parametrize("backend,devices", [
    pytest.param("jax", 2, id="jax-2-default"),
    pytest.param("jax", 4, id="jax-4-default"),     # a v5e 2x2 host
    pytest.param("jax", 8, id="jax-8-default"),
    pytest.param("jax_x64", 8, id="jax_x64-8-default"),
])
def test_sharded_backend_matches_numpy_oracle(backend, devices):
    """Every sharded lane is bit-identical with the numpy oracle —
    argmin config, cost, and first-minimum tie-breaking — on random,
    ragged, tie-heavy, and all-infeasible grids."""
    out = _run_sharded_lane(backend, devices)
    assert out["devices"] == devices
    assert out["ok"], out["bad"]


# The span-boundary tie on the mesh: a 1,240-row grid in spans of 200 rows
# (4 shards of 50), so the scan loops over 7 spans on the device, the last
# partial.  Equal minima at rows 1210 (span 6, shard 0), 260 (span 1,
# shard 1) and 700 (span 3, shard 2): row 260 must win, not the lowest
# shard of a later span.
_SPAN_TIE_CHILD = """
import json, sys
import numpy as np
import jax
import jax.numpy as jnp
from repro.core import planning_backend as pb
from repro.core.cluster import ClusterConditions, ResourceDim
from repro.obs import get_metrics

name, want = sys.argv[1], int(sys.argv[2])
be = pb.get_backend(name)
assert be.device_count() == want, (be.device_count(), want)
pb.MAX_LIVE_ELEMENTS = 2 * 50               # (Qpad 2) x 50 rows a shard
cluster = ClusterConditions(dims=(ResourceDim("a", 3, 159, step=4),
                                  ResourceDim("b", 2, 92, step=3)))
shape = tuple(len(d.grid()) for d in cluster.dims)
table = np.random.default_rng(17).integers(8, 64, size=shape).astype(float)
table.reshape(-1)[[1210, 260, 700]] = 2.0


def fn_of(xp):
    ga, gb = (xp.asarray(np.asarray(d.grid())) for d in cluster.dims)
    t = xp.asarray(table)

    def fn(cfgs, params=None):
        a = xp.asarray(cfgs)
        c = t[xp.searchsorted(ga, a[:, 0]), xp.searchsorted(gb, a[:, 1])]
        return c if params is None else c + params[0]
    return fn


k = int(np.argmin(table.reshape(-1)))
cfg = [int(v) for v in pb.enumerate_configs(cluster, k, k + 1)[0]]
pm = np.array([[0.0], [3.0]])
mx = get_metrics()
got = [be.argmin_grid(fn_of(jnp), cluster, chunk_size=50)]
got += be.argmin_grid_many(fn_of(jnp), cluster, pm)
snap = mx.snapshot()
print(json.dumps({"winner": k, "cfg": cfg,
                  "got": [[list(r), c] for r, c in got],
                  "launches": snap.get("backend.launches", 0),
                  "spans": snap.get("backend.scan_spans", 0)}))
"""


@needs_jax
@pytest.mark.parametrize("backend", ["jax", "jax_x64"])
def test_sharded_looped_scan_ties_across_spans_take_the_lower_row(backend):
    """On a 4-device mesh a scan that loops over 7 spans returns the
    first of minima tied across spans, through ``argmin_grid`` and
    ``argmin_grid_many``, in one launch per scan."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("REPRO_PLAN_DEVICES", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SPAN_TIE_CHILD, backend, "4"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["winner"] == 260
    assert out["got"] == [[out["cfg"], 2.0], [out["cfg"], 2.0],
                          [out["cfg"], 5.0]]
    assert (out["launches"], out["spans"]) == (2, 14)


# -------------------- single-device (rollback) path ------------------------- #

@needs_jax
def test_plan_devices_env_is_the_rollback_switch(monkeypatch):
    from repro.core.planning_backend import JaxPlanBackend
    from repro.launch.mesh import plan_device_count
    monkeypatch.setenv("REPRO_PLAN_DEVICES", "1")
    assert plan_device_count() == 1
    assert JaxPlanBackend().device_count() == 1
    monkeypatch.setenv("REPRO_PLAN_DEVICES", "not-a-number")
    assert plan_device_count() >= 1        # malformed cap is ignored


@needs_jax
def test_devices_ctor_cap_and_shard_mode_off():
    """``devices=1`` caps the plan mesh at one device: the scans build
    their single-device programs, with no ``shard_map``."""
    import jax.numpy as jnp
    from repro.core.planning_backend import JaxPlanBackend
    be = JaxPlanBackend(devices=1)
    assert be.device_count() == 1
    def fn(cfgs, p):
        return jnp.asarray(cfgs)[:, 0] * 1.0 + p[0]
    be.argmin_grid_many(fn, paper_cluster(9, 4), np.zeros((2, 1)))
    ((key, (_, prog)),) = be._programs.items()
    assert key[0] == "scan_many" and key[3][-1] == 1      # D in the key
    text = prog.lower(0, be._params(np.zeros((2, 1)))).as_text()
    assert "sdy.manual" not in text and "SPMDFullToShardShape" not in text


# ------------------------ _many_chunk geometry ------------------------------ #

def test_many_chunk_floors_large_q_to_min_shard_rows():
    """chunk_size // Q used to floor to single-digit rows for large Q —
    pure dispatch overhead; the floor keeps shards worth dispatching."""
    assert _many_chunk(10 ** 9, 4096, 1, 1 << 20) == MIN_SHARD_ROWS
    assert _many_chunk(10 ** 9, 4096, 8, 1 << 20) == MIN_SHARD_ROWS


def test_many_chunk_caps_live_elements():
    """The (Q, chunk) live cost block per dispatch stays bounded."""
    got = _many_chunk(10 ** 9, 8, 1, 1 << 23)
    assert got == MAX_LIVE_ELEMENTS // 8
    assert got * 8 <= MAX_LIVE_ELEMENTS


def test_many_chunk_clips_to_per_device_share():
    assert _many_chunk(100, 1, 8, 1 << 20) == 13     # ceil(100 / 8)
    assert _many_chunk(100, 1, 1, 1 << 20) == 100
    assert _many_chunk(12, 0, 4, 1 << 20) == 3       # Q=0 guarded to 1


def test_padding_helpers():
    assert [_pad_even(n) for n in (1, 2, 3, 4)] == [2, 2, 4, 4]
    assert _pad_multiple(5, 8) == 8 and _pad_multiple(8, 8) == 8
    assert _pad_multiple(9, 8) == 16


# ------------------- double-buffered broker identity ------------------------ #

def _costing(broker=None, cache=None, mode="batched"):
    return OperatorCosting(models=simulator_cost_models(),
                           cluster=paper_cluster(40, 10),
                           resource_planning=mode, broker=broker,
                           cache=cache)


def _tree_sig(p):
    if p is None:
        return None
    if p.is_leaf:
        return tuple(sorted(p.tables))
    return (p.impl, p.resources, p.op_cost, p.total_cost,
            _tree_sig(p.left), _tree_sig(p.right))


class _LegacyBroker(PlanBroker):
    """A broker WITHOUT flush_async: drives the planners' non-pipelined
    fallback branch (property with no getter -> AttributeError)."""
    flush_async = property()


WAVE1 = [("SMJ", 2.0, 74.0), ("BHJ", 1.0, 74.0)]
WAVE2 = [("SMJ", 3.0, 50.0), ("BHJ", 0.5, 20.0), ("SMJ", 2.0, 74.0)]


def test_flush_async_waves_identical_with_sequential_flush():
    """Two flush_async waves == two sequential flushes, bit-for-bit:
    plans, cache contents, cache counters, broker stats.  Wave N's
    commits must precede wave N+1's cache lookups (the two-phase
    interpolating-cache contract survives double buffering)."""
    results, caches, brokers = {}, {}, {}
    for label, dbl in (("seq", False), ("dbl", True)):
        cache = ResourcePlanCache("exact")
        broker = PlanBroker("numpy", double_buffer=dbl)
        c = _costing(broker=broker, cache=cache)
        for op in WAVE1:
            c.prefetch(*op)
        broker.flush_async() if dbl else broker.flush()
        for op in WAVE2:
            c.prefetch(*op)
        broker.flush_async() if dbl else broker.flush()
        results[label] = [c.plan_resources(*op) for op in WAVE1 + WAVE2]
        caches[label], brokers[label] = cache, broker
    assert results["dbl"] == results["seq"]
    assert brokers["dbl"].inflight_count() == 0
    assert caches["dbl"]._store.keys() == caches["seq"]._store.keys()
    for k in caches["seq"]._store:
        assert caches["dbl"]._store[k].keys == caches["seq"]._store[k].keys
        assert caches["dbl"]._store[k].configs \
            == caches["seq"]._store[k].configs
    assert caches["dbl"].counters_snapshot() \
        == caches["seq"].counters_snapshot()
    for f in ("broker_requests", "broker_dedup_hits", "broker_batches"):
        assert getattr(brokers["dbl"].stats, f) \
            == getattr(brokers["seq"].stats, f), f


def test_flush_async_leaves_wave_in_flight_until_first_result():
    broker = PlanBroker("numpy")
    c = _costing(broker=broker)
    for op in WAVE1:
        c.prefetch(*op)
    broker.flush_async()
    assert broker.pending_count() == 0
    assert broker.inflight_count() == len(WAVE1)   # wave futures pending
    r = c.plan_resources(*WAVE1[0])          # commits the in-flight wave
    assert broker.inflight_count() == 0
    assert r == _costing().plan_resources(*WAVE1[0])


def test_flush_async_degrades_to_flush_without_double_buffer():
    broker = PlanBroker("numpy", double_buffer=False)
    c = _costing(broker=broker)
    c.prefetch(*WAVE1[0])
    broker.flush_async()
    assert broker.pending_count() == 0
    assert broker.inflight_count() == 0      # nothing left un-committed


def test_plain_flush_commits_any_inflight_wave_first():
    """flush() after flush_async() must commit the in-flight wave before
    the new one (submission order), never drop or reorder it."""
    broker = PlanBroker("numpy")
    c = _costing(broker=broker)
    for op in WAVE1:
        c.prefetch(*op)
    broker.flush_async()
    for op in WAVE2:
        c.prefetch(*op)
    broker.flush()
    assert broker.inflight_count() == 0 and broker.pending_count() == 0
    seq = _costing(broker=PlanBroker("numpy", double_buffer=False))
    assert [c.plan_resources(*op) for op in WAVE1 + WAVE2] \
        == [seq.plan_resources(*op) for op in WAVE1 + WAVE2]


# --------------- pipelined planners == serial == legacy --------------------- #

@pytest.mark.parametrize("seed", [3, 11])
def test_selinger_pipelined_identical_across_broker_modes(seed):
    """The level-ahead Selinger pipeline (stand-in cardinalities) must
    produce the same plan AND the same broker traffic as the serial-flush
    and legacy (non-pipelined) paths: equal request counts prove every
    stand-in prefetch key matched the real enumeration exactly."""
    schema = random_schema(6, seed=seed)
    q = random_query(schema, 5, seed=seed)
    sigs, traffic = [], []
    for broker in (PlanBroker("numpy"),
                   PlanBroker("numpy", double_buffer=False),
                   _LegacyBroker("numpy")):
        c = _costing(broker=broker)
        sigs.append(_tree_sig(selinger_plan(schema, q, c)))
        traffic.append((broker.stats.broker_requests,
                        broker.stats.broker_dedup_hits,
                        c.stats.cache_hits, c.stats.cache_misses))
    assert sigs[0] == sigs[1] == sigs[2]
    assert traffic[0] == traffic[1] == traffic[2]


def test_fast_randomized_pipelined_identical_across_broker_modes():
    schema = random_schema(7, seed=5)
    q = random_query(schema, 4, seed=5)
    ref = None
    for broker in (PlanBroker("numpy"), _LegacyBroker("numpy")):
        best, archive = fast_randomized_plan(schema, q,
                                             _costing(broker=broker),
                                             seed=5)
        sig = (_tree_sig(best), [_tree_sig(p) for p in archive.plans])
        ref = sig if ref is None else ref
        assert sig == ref
