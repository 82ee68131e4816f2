"""Lockstep cross-query planning parity (RAQO.plan_queries lockstep=True):
advancing every concurrent query one DP level (or mutation round) per
shared flush wave must be BIT-IDENTICAL to sequential per-query planning
— plans, costs, resource-plan cache contents and counters, and broker
traffic — across ragged query sizes (single-table included), shared
caches, disconnected cross-join fallbacks, both planners, legacy
(non-double-buffered) brokers, and 8 simulated devices.

The authoritative baseline is the sequential per-query loop: ONE RAQO
(shared cache + broker + compiled-fn caches) calling ``joint()`` per
query — exactly what a tenant submitting queries one at a time runs.
The PR 7 per-query pipeline (``lockstep=False``) is compared on plans
and on miss/insert counters only: its upfront base prefetch is orphaned
by each query's ``begin_query()``, so queries resubmit those requests
and the resubmissions count extra cache HITS (a pre-existing baseline
quirk the lockstep driver does not reproduce).

Wave accounting (PlanBroker.counters_snapshot) is asserted here too:
lockstep must do the same work in FEWER, LARGER waves.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis.recompile_audit import expected_compile_counts
from repro.core.cluster import paper_cluster
from repro.core.plan_broker import PlanBroker
from repro.core.plan_cache import ResourcePlanCache
from repro.core.raqo import RAQO
from repro.core.schema import (JoinEdge, Relation, Schema, random_query,
                               random_schema)

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _raqo(schema, broker, *, cache=None, planner="selinger", backend=None):
    return RAQO(schema, cluster=paper_cluster(24, 8), planner=planner,
                resource_planning="batched", cache=cache, backend=backend,
                broker=broker)


def _tree_sig(p):
    if p is None:
        return None
    if p.is_leaf:
        return tuple(sorted(p.tables))
    return (p.impl, p.resources, p.op_cost, p.total_cost,
            _tree_sig(p.left), _tree_sig(p.right))


def _sigs(joint_plans):
    return [_tree_sig(jp.plan) for jp in joint_plans]


class _LegacyBroker(PlanBroker):
    """A broker WITHOUT flush_async: drives the lockstep driver's
    queue-then-flush-per-level fallback branch."""
    flush_async = property()


# ----------------- lockstep == sequential per-query joint ------------------- #

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_hypothesis_lockstep_matches_sequential_joint(seed):
    """Cache-less numpy identity on random schemas and RAGGED query
    batches (sizes 1..5 — single-table queries retire at construction):
    plans, predicted times, and money all bit-equal the one-RAQO
    sequential joint() loop."""
    rng = np.random.default_rng(seed)
    schema = random_schema(8, seed=seed % 100)
    sizes = [int(rng.integers(1, 6)) for _ in range(4)]
    queries = [random_query(schema, k, seed=seed + i)
               for i, k in enumerate(sizes)]
    got = _raqo(schema, PlanBroker("numpy")).plan_queries(queries)
    r_seq = _raqo(schema, PlanBroker("numpy"))
    exp = [r_seq.joint(q) for q in queries]
    assert _sigs(got) == _sigs(exp)
    assert [(g.exec_time, g.money) for g in got] == \
        [(e.exec_time, e.money) for e in exp]


def test_lockstep_matches_sequential_joint_with_shared_cache():
    """With a shared exact resource-plan cache, lockstep equals the
    sequential loop on EVERYTHING observable: plans, per-(model, kind)
    hit/miss/insert counters, the cache's stored keys and configs, and
    the broker's request/dedup totals."""
    schema = random_schema(9, seed=3)
    queries = [random_query(schema, k, seed=q)
               for q, k in enumerate((5, 3, 5, 4, 1, 5))]
    runs = {}
    for label in ("lockstep", "sequential"):
        cache = ResourcePlanCache("exact")
        broker = PlanBroker("numpy")
        r = _raqo(schema, broker, cache=cache)
        if label == "lockstep":
            plans = r.plan_queries(queries)
        else:
            plans = [r.joint(q) for q in queries]
        runs[label] = (plans, cache, broker)
    (gp, gc, gb), (ep, ec, eb) = runs["lockstep"], runs["sequential"]
    assert _sigs(gp) == _sigs(ep)
    assert gc.counters_snapshot() == ec.counters_snapshot()
    assert set(gc._store) == set(ec._store)
    for k in gc._store:
        assert gc._store[k].keys == ec._store[k].keys
        assert gc._store[k].configs == ec._store[k].configs
    gs, es = gb.counters_snapshot(), eb.counters_snapshot()
    assert (gs["requests"], gs["dedup_hits"]) == \
        (es["requests"], es["dedup_hits"])


def test_lockstep_matches_per_query_pipeline():
    """Against the PR 7 per-query pipeline (lockstep=False): identical
    plans; cache-less broker traffic equal modulo dedup (requests minus
    dedup hits — the searches actually run — match); with a shared cache,
    misses and inserts equal while the baseline's hits are inflated by
    its orphaned upfront prefetch (see module docstring)."""
    schema = random_schema(9, seed=5)
    queries = [random_query(schema, 5, seed=q) for q in range(4)]
    b1, b2 = PlanBroker("numpy"), PlanBroker("numpy")
    got = _raqo(schema, b1).plan_queries(queries, lockstep=True)
    exp = _raqo(schema, b2).plan_queries(queries, lockstep=False)
    assert _sigs(got) == _sigs(exp)
    s1, s2 = b1.counters_snapshot(), b2.counters_snapshot()
    assert s1["requests"] - s1["dedup_hits"] == \
        s2["requests"] - s2["dedup_hits"]

    counters = {}
    for lockstep in (True, False):
        cache = ResourcePlanCache("exact")
        r = _raqo(schema, PlanBroker("numpy"), cache=cache)
        plans = r.plan_queries(queries, lockstep=lockstep)
        counters[lockstep] = cache.counters_snapshot()
        assert _sigs(plans) == _sigs(got)
    assert set(counters[True]) == set(counters[False])
    for k, c in counters[True].items():
        assert c["misses"] == counters[False][k]["misses"]
        assert c["inserts"] == counters[False][k]["inserts"]
        assert c["hits"] <= counters[False][k]["hits"]


def test_lockstep_disconnected_cross_join_fallback():
    """Disconnected queries take the one-cross-join fallback inside their
    final consume; mixed with connected (and fully edge-less) queries in
    one ragged lockstep batch, plans still equal the sequential loop."""
    rels = {n: Relation(n, 200_000 + 170_000 * i, 110 + 12 * i)
            for i, n in enumerate("abcde")}
    edges = [JoinEdge("a", "b", 1e-6), JoinEdge("b", "c", 2e-6)]
    schema = Schema(rels, edges)          # components {a,b,c}, {d}, {e}
    queries = [["a", "b", "c", "d"],      # one cross join at the top
               ["a", "b"],                # connected
               ["d", "e"],                # no edges at all
               ["a", "b", "c"]]
    assert not schema.connected(queries[0])
    got = _raqo(schema, PlanBroker("numpy")).plan_queries(queries)
    r_seq = _raqo(schema, PlanBroker("numpy"))
    exp = [r_seq.joint(q) for q in queries]
    assert _sigs(got) == _sigs(exp)
    assert all(jp.plan is not None for jp in got)


def test_lockstep_fastrandomized_identical():
    """FastRandomized lockstep (round-interleaved mutation prefetch) ==
    per-query pipeline == sequential joint: per-session RNG streams make
    the interleaving invisible."""
    schema = random_schema(8, seed=2)
    queries = [random_query(schema, k, seed=q)
               for q, k in enumerate((5, 3, 4))]
    r1 = _raqo(schema, PlanBroker("numpy"), planner="fastrandomized")
    got = r1.plan_queries(queries, lockstep=True)
    r2 = _raqo(schema, PlanBroker("numpy"), planner="fastrandomized")
    base = r2.plan_queries(queries, lockstep=False)
    r3 = _raqo(schema, PlanBroker("numpy"), planner="fastrandomized")
    seq = [r3.joint(q) for q in queries]
    assert _sigs(got) == _sigs(base) == _sigs(seq)


def test_lockstep_legacy_broker_identical():
    """A broker without flush_async drives the queue-then-flush-per-level
    fallback: one wave per DP level, same plans."""
    schema = random_schema(8, seed=7)
    queries = [random_query(schema, k, seed=q)
               for q, k in enumerate((4, 5, 2))]
    sigs = []
    for broker in (PlanBroker("numpy"), _LegacyBroker("numpy")):
        sigs.append(_sigs(_raqo(schema, broker).plan_queries(queries)))
    r_seq = _raqo(schema, PlanBroker("numpy"))
    sigs.append(_sigs([r_seq.joint(q) for q in queries]))
    assert sigs[0] == sigs[1] == sigs[2]


# --------------------------- wave accounting -------------------------------- #

def test_wave_accounting_snapshot_consistency():
    """counters_snapshot exposes the wave ledger; every request that is
    not resolved at submit time (session-memo hit) rides exactly one
    wave; lockstep does the same work in FEWER, LARGER waves than the
    sequential per-query loop."""
    schema = random_schema(9, seed=1)
    queries = [random_query(schema, 5, seed=q) for q in range(6)]
    b_lock, b_seq = PlanBroker("numpy"), PlanBroker("numpy")
    _raqo(schema, b_lock).plan_queries(queries)
    r_seq = _raqo(schema, b_seq)
    for q in queries:
        r_seq.joint(q)
    for snap in (b_lock.counters_snapshot(), b_seq.counters_snapshot()):
        assert set(snap) == {"requests", "dedup_hits", "batches", "waves",
                             "wave_sizes", "max_wave", "mean_wave"}
        assert snap["waves"] == len(snap["wave_sizes"])
        # submit-time memo hits (a subset of dedup_hits) never enter a
        # wave; everything else rides exactly one
        assert snap["requests"] - snap["dedup_hits"] \
            <= sum(snap["wave_sizes"]) <= snap["requests"]
        assert snap["max_wave"] == max(snap["wave_sizes"])
        assert snap["mean_wave"] == round(
            sum(snap["wave_sizes"]) / len(snap["wave_sizes"]), 3)
    lock, seq = b_lock.counters_snapshot(), b_seq.counters_snapshot()
    assert lock["waves"] < seq["waves"]
    assert lock["mean_wave"] > seq["mean_wave"]


def test_level1_fanout_submits_base_candidates_once():
    """Recurring identical queries: the base-level fan-out ("queue once,
    fan the future out") submits each distinct base candidate a single
    time, so lockstep broker traffic shrinks below the sequential loop's
    while requests-minus-dedup (searches actually run) and plans stay
    identical."""
    schema = random_schema(8, seed=4)
    q = random_query(schema, 5, seed=0)
    queries = [list(q), list(q), list(q)]
    b_lock, b_seq = PlanBroker("numpy"), PlanBroker("numpy")
    got = _raqo(schema, b_lock).plan_queries(queries)
    r_seq = _raqo(schema, b_seq)
    exp = [r_seq.joint(t) for t in queries]
    assert _sigs(got) == _sigs(exp)
    assert _sigs(got)[0] == _sigs(got)[1] == _sigs(got)[2]
    sl, ss = b_lock.counters_snapshot(), b_seq.counters_snapshot()
    assert sl["requests"] < ss["requests"]
    assert sl["requests"] - sl["dedup_hits"] == \
        ss["requests"] - ss["dedup_hits"]


# ------------------------- recompile contract ------------------------------- #

def test_lockstep_recompile_contract_frozen():
    """Lockstep adds NO program shapes beyond Q-stacking: one new audit
    probe (lockstep_wave_qpad) covering varying per-wave Q, and every
    pre-existing probe expectation untouched — frozen here at D=1 and
    D=8 so drift fails loudly."""
    legacy = {"scan_params_reuse", "scan_chunk_churn", "scan_many_qpad",
              "climb_params_reuse", "climb_many_qpad", "grid_rekey"}
    for be in ("numpy", "jax", "jax_x64"):
        d1 = expected_compile_counts(be, 1)
        assert set(d1) == legacy | {"lockstep_wave_qpad"}
        assert d1["lockstep_wave_qpad"] == (0 if be == "numpy" else 3)
    frozen_d8 = {"scan_params_reuse": 1, "scan_chunk_churn": 1,
                 "scan_many_qpad": 3, "climb_params_reuse": 1,
                 "climb_many_qpad": 1, "grid_rekey": 2,
                 "lockstep_wave_qpad": 3}
    assert expected_compile_counts("jax", 8) == frozen_d8
    assert expected_compile_counts("jax_x64", 8) == frozen_d8
    assert all(v == 0 for v in expected_compile_counts("numpy", 8).values())


# ------------------------- backend-matrix lane ------------------------------ #

def test_lockstep_identical_on_lane_backend(plan_backend,
                                            plan_backend_name):
    """The CI matrix lane's backend (REPRO_PLAN_BACKEND) plans the same
    batch identically lockstep vs sequential — argmin-identical search
    makes this exact on every backend."""
    schema = random_schema(8, seed=6)
    queries = [random_query(schema, k, seed=q)
               for q, k in enumerate((4, 3, 4))]
    broker = PlanBroker(plan_backend_name)
    got = _raqo(schema, broker,
                backend=plan_backend_name).plan_queries(queries)
    r_seq = _raqo(schema, PlanBroker(plan_backend_name),
                  backend=plan_backend_name)
    exp = [r_seq.joint(q) for q in queries]
    assert _sigs(got) == _sigs(exp)
    assert broker.counters_snapshot()["waves"] > 0


# -------------------- 8-simulated-device subprocess lane -------------------- #

_LOCKSTEP_DRIVER = """
import json, sys
import jax
from repro.core.cluster import paper_cluster
from repro.core.plan_broker import PlanBroker
from repro.core.raqo import RAQO
from repro.core.schema import random_query, random_schema

want = int(sys.argv[1])
assert jax.device_count() == want, (jax.device_count(), want)

schema = random_schema(8, seed=3)
queries = [random_query(schema, k, seed=q)
           for q, k in enumerate((5, 3, 1, 4, 5))]


def raqo(broker):
    return RAQO(schema, cluster=paper_cluster(24, 8), backend="jax",
                resource_planning="batched", broker=broker)


def sig(p):
    if p is None:
        return None
    if p.is_leaf:
        return sorted(p.tables)
    return [p.impl, list(p.resources), p.op_cost, p.total_cost,
            sig(p.left), sig(p.right)]


b_lock = PlanBroker("jax")
lock = raqo(b_lock).plan_queries(queries)
b_seq = PlanBroker("jax")
r_seq = raqo(b_seq)
seq = [r_seq.joint(q) for q in queries]
sl, ss = b_lock.counters_snapshot(), b_seq.counters_snapshot()
out = {"devices": jax.device_count(),
       "sigs_equal": [sig(a.plan) for a in lock] == [sig(b.plan)
                                                     for b in seq],
       "searches_equal": (sl["requests"] - sl["dedup_hits"]
                          == ss["requests"] - ss["dedup_hits"]),
       "fewer_waves": sl["waves"] < ss["waves"],
       "lock": sl, "seq": ss}
out["ok"] = (out["sigs_equal"] and out["searches_equal"]
             and out["fewer_waves"])
print(json.dumps(out))
"""


@needs_jax
def test_lockstep_parity_at_8_simulated_devices():
    """Device-sharded lane: lockstep == sequential joint on plans and
    broker searches at 8 simulated XLA devices, with fewer waves."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("REPRO_PLAN_DEVICES", None)
    proc = subprocess.run(
        [sys.executable, "-c", _LOCKSTEP_DRIVER, "8"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["devices"] == 8
    assert out["ok"], out
