"""Session-scoped planning broker: one fused program call plans every
operator of every concurrent query.

The paper's architecture (Fig. 8) invokes resource planning once *per
operator per query*; even with the jitted array backend (PR 2) that is
one XLA program dispatch per request, and the §VII-C 100K-container story
multiplies it by every operator of every query in flight.  This module
breaks that per-request wall: callers (the DB-domain ``OperatorCosting``,
the TPU-domain ``ShardingPlanner``, and the ``RAQO`` facade's multi-query
entry point) *defer* their planning requests to a shared per-session
broker, which resolves them in three stages mapping onto the paper's §VI
machinery:

1. **Dedup / cache fronting (§VI-B3).**  Requests are resolved against
   the ``ResourcePlanCache`` first (same lookup modes, same stats), and
   requests that share a cache key — or, for cache-less callers, the
   exact (cost-fn, params, mode) signature — collapse onto one *leader*
   search; followers reuse the leader's configuration and re-cost it
   through their own scalar float64 path, exactly like a sequential
   cache hit would.  Cache-less results additionally persist in a
   bounded session memo, so recurring jobs across queries (the paper's
   §V story) never re-search.

2. **Stacked search (§VI-B1/2).**  Surviving leaders are grouped by
   (cost-fn object, grid) and their per-request scalars stacked into a
   padded ``(Q, P)`` params array; each group then runs as ONE array
   program on the selected ``PlanBackend`` — ``argmin_grid_many`` (the
   vectorized exhaustive scan of §VI-B1, all Q requests per chunk) or
   ``hill_climb_ensemble_many`` (the batched Algorithm 1 of §VI-B2,
   every start of every request climbing in one vmapped jitted
   ``while_loop``).  On the numpy backend the stacked arithmetic is
   bit-identical with Q independent per-operator searches (argmin ties
   included); on jax the whole group is one fused program dispatch.

3. **Commit / fan-out.**  Each winner is re-evaluated through the
   caller's scalar float64 cost fn before being fanned back to the
   caller's future.  A float32 jax winner that turns out infeasible in
   float64 is redone exactly on the numpy backend (same fallback the
   per-operator path used); on *exact* backends (numpy, ``jax_x64``)
   that fallback is a parity assertion.  Ensemble requests stranded on
   an all-infeasible plateau rerun as a grid scan (stacked again) when
   ``scan_fallback`` is set.  Freshly searched feasible plans are
   inserted into the cache, so the next flush dedups against them.

Semantics note: broker results are sequential-identical for *every*
cache mode.  Exact-mode caches (and cache-less requests) resolve their
lookups at flush entry — within-flush sharing is pure leader/follower
dedup, bit-identical to the sequential loop.  Nearest-neighbor and
weighted-average caches interpolate, so their lookups must observe
entries inserted *earlier in the same flush*; those requests are
therefore planned two-phase: stage 2 still runs their searches stacked
(speculatively, one fused program with everything else), but the cache
lookup is re-done per request in submission order during stage 3 — a
request whose re-lookup hits (possibly against a same-flush insert)
takes the hit exactly as the sequential loop would, and the speculative
search result is committed (and inserted) only otherwise.  Cached
requests sharing a key with an *earlier same-flush* request take the
same per-request stage-3 replay whatever the cache mode: an exact-mode
duplicate must count one miss on the leader and one HIT on the
follower (its sequential lookup would see the leader's fresh insert),
not two entry-time misses — the lockstep multi-query driver
(repro.core.raqo ``plan_queries``) routinely puts every query's
level-L copy of a recurring operator in one wave, and its cache
counters must still match per-query sequential planning exactly.
Plans, costs, cache contents, and cache hit/miss counters all match
the sequential per-operator loop; only ``configs_explored`` may exceed
it for interpolating caches (discarded speculative searches are still
counted as work done).  The property tests in
tests/test_plan_broker.py and tests/test_lockstep.py pin this.  If a
leader's search comes back infeasible (nothing insertable), its
followers are re-planned one by one through the sequential semantics,
so that corner matches the per-operator loop too.

Double-buffered flushes: stage 2 is internally split into *dispatch*
(group, stack, launch the array programs — backends expose this half as
``argmin_grid_many_async`` / ``hill_climb_ensemble_many_async``) and
*finalize* (the single host sync reading the winners back).
``flush_async()`` commits the previous in-flight wave, dispatches the
currently pending requests as the new wave, and returns WITHOUT syncing:
the driver (``selinger_join_order``'s next DP level, FastRandomized's
next generation) enumerates wave N+1 while wave N's programs run on
device.  Commit order is preserved exactly — wave N's stage-3 commits
(float64 re-cost, cache inserts, future resolution, in submission
order) always complete before wave N+1's stage-1 cache lookups, so
plans, cache contents, and hit/miss counters are bit-identical to
calling ``flush()`` at the same points; ``PlanFuture.result()`` on an
in-flight request commits just that wave.  ``double_buffer=False``
degrades ``flush_async`` to ``flush``.  Within a *synchronous* flush
the same split still pays: every (fn, grid) group's program is
dispatched before any group's results are read back, so e.g. a flush
mixing SMJ and BHJ operators overlaps the two scans.

Instrumentation (``repro.obs``): a wave is the spans ``broker.stage1``
(dedup, memo and cache fronting), ``broker.dispatch`` (around each
``broker.dispatch.group``), ``broker.wave.execute`` (the sync, around
each ``broker.group.sync``) and ``broker.wave.commit``, all opened on
the thread's span stack and all carrying the broker ``wave`` number, so
a double-buffered wave's dispatch, sync and commit share an identifier.
A synchronous flush opens ``broker.flush.sync`` with its ``cause``:
``result`` (a ``PlanFuture.result()`` on a pending request), ``retry``
(the ensemble -> grid ``scan_fallback`` rerun) or ``explicit`` (any
other ``flush()`` caller).  Always on, one increment per flush or
stacked group: ``broker.sync_flushes.<cause>`` and the grid rows each
stacked scan group sweeps, once per group, by the kind of flush that
dispatched it (``broker.sweep_rows.sync`` / ``broker.sweep_rows.async``).
Traced, a request keeps its submit stamp, its verdict and its wave;
the wave's stamps serve ``PlanFuture.critical_path()``.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.analysis.registry import hot_path
from repro.core.cluster import ClusterConditions, PlanningStats
from repro.core.plan_cache import ResourcePlanCache
from repro.core.planning_backend import (BatchCostFn, PlanBackend, Result,
                                         get_backend)
from repro.obs import get_metrics, get_tracer

ScalarCostFn = Callable[[Tuple[int, ...]], float]

# bound once at import; enable/disable flips the singletons in place.
# Disabled-tracer cost on the flush hot loop: one attribute load + branch
# per instrumentation point (no kwargs dicts, no clock reads — pinned
# allocation-free by tests/test_obs.py)
_obs = get_tracer()
_metrics = get_metrics()


class _Stamps:
    """Tracing-on record of one broker wave, shared by every request it
    carries: its number and the ``perf_counter_ns`` at which stage 1
    ended, its programs were dispatched, its sync returned and its
    commit ended (0 where the wave never got there)."""

    __slots__ = ("wave", "stage1", "dispatch", "execute", "commit")

    def __init__(self, wave: int):
        self.wave = wave
        self.stage1 = self.dispatch = self.execute = self.commit = 0


def _wave_futures(order):
    """Every future a wave's stage 3 resolves (leaders, their followers,
    replayed followers)."""
    for role, entry in order:
        if role == "dfollower":
            yield entry[1]
        else:
            yield entry.fut
            for _, f in entry.followers:
                yield f


def _observe_latencies(futs, t_ns: int) -> None:
    """Tracing-on: feed the broker's tail histogram (submit -> resolve)
    with one lock for the requests resolved at ``t_ns``."""
    _metrics.histogram("broker.request_s").observe_many(
        [(t_ns - f.submit_ns) / 1e9 for f in futs
         if f.submit_ns is not None])


@dataclasses.dataclass
class PlanRequest:
    """One deferred resource-planning request.

    ``fn`` is the param-style batch cost surface (``fn(configs, params)``
    -> costs, traceable for jax backends); ``params`` the per-request
    scalars (e.g. ``[ss, ls]`` or ``[chip_budget, max_chips]``);
    ``commit_fn`` the scalar float64 cost of one configuration (the
    commit/validation path, never inside the search); ``fallback_fn`` a
    numpy-namespace twin of ``fn`` used to redo the search exactly when a
    non-exact backend's winner fails the float64 commit."""
    fn: BatchCostFn
    cluster: ClusterConditions
    params: np.ndarray
    commit_fn: ScalarCostFn
    mode: str = "grid"                 # "grid" | "ensemble"
    n_random: int = 0
    seed: int = 0
    scan_fallback: bool = False        # ensemble all-inf -> grid scan
    fallback_fn: Optional[BatchCostFn] = None
    cache: Optional[ResourcePlanCache] = None
    cache_key: Optional[Tuple[str, str, float]] = None
    validate_hit: bool = False         # reject infeasible cache hits
    stats: Optional[PlanningStats] = None

    def __post_init__(self):
        self.params = np.asarray(self.params, dtype=np.float64)


class PlanFuture:
    """Handle to a deferred plan; ``result()`` flushes the broker if the
    request is still pending and returns ``(resources, cost)``.

    When tracing is enabled at submit time, ``submit_ns`` holds the
    submit stamp (``perf_counter_ns``), ``verdict`` the broker's
    decision and ``wave`` the stamps of the wave that resolved it, and
    ``critical_path()`` reports the latency breakdown; with tracing off
    all three stay None."""

    __slots__ = ("_broker", "done", "value", "submit_ns", "verdict", "wave")

    def __init__(self, broker: "PlanBroker"):
        self._broker = broker
        self.done = False
        self.value: Result = (None, math.inf)
        self.submit_ns: Optional[int] = None
        self.verdict: Optional[str] = None
        self.wave: Optional[_Stamps] = None

    def result(self) -> Result:
        if not self.done:
            self._broker._ensure(self)
        if not self.done:
            raise RuntimeError("broker flush did not resolve this request")
        return self.value

    def critical_path(self) -> Optional[dict]:
        """Latency breakdown of this request (None when tracing was off
        at submit): ``verdict`` (memo / cache-hit / leader / follower /
        replay / dleader), ``wave`` number, and the seconds split, read
        from the wave's stamps — ``queue_s`` (submit -> wave dispatch),
        ``execute_s`` (dispatch -> wave sync), ``commit_s`` (sync ->
        the wave's commit end), ``total_s``.  A memo hit at submit
        resolves inside ``submit()`` (``total_s`` 0, no wave); memo and
        cache hits of stage 1 resolve when stage 1 ends, so they carry
        ``total_s`` alone."""
        sub = self.submit_ns
        if sub is None:
            return None
        w = self.wave
        out: dict = {"verdict": self.verdict or "pending",
                     "wave": None if w is None else w.wave}
        if w is None:
            if self.done:
                out["total_s"] = 0.0
            return out
        if self.verdict in ("memo", "cache-hit"):
            if w.stage1:
                out["total_s"] = (w.stage1 - sub) / 1e9
            return out
        if w.commit:
            out["total_s"] = (w.commit - sub) / 1e9
        if w.dispatch:
            out["queue_s"] = (w.dispatch - sub) / 1e9
        if w.dispatch and w.execute:
            out["execute_s"] = (w.execute - w.dispatch) / 1e9
        if w.execute and w.commit:
            out["commit_s"] = (w.commit - w.execute) / 1e9
        return out


@dataclasses.dataclass
class _Exec:
    """A leader request plus the followers deduplicated onto it."""
    req: PlanRequest
    fut: PlanFuture
    followers: List[Tuple[PlanRequest, PlanFuture]] = \
        dataclasses.field(default_factory=list)
    res: Optional[Tuple[int, ...]] = None
    cost: float = math.inf


@dataclasses.dataclass
class _Wave:
    """One dispatched-but-uncommitted flush wave (the double buffer):
    its programs are in flight on device; ``finalize`` syncs them, after
    which stage 3 commits ``order``.  ``futs`` holds the ``id()`` of
    every future the wave will resolve, so ``PlanFuture.result()`` can
    commit exactly this wave without flushing newer pending work."""
    order: List[Tuple[str, object]]
    execs: List[_Exec]
    finalize: Callable[[], None]
    futs: frozenset
    wave_no: int = 0
    stamps: Optional[_Stamps] = None


class PlanBroker:
    """Collects planning requests from every operator of every query in
    flight and resolves them in batched flushes (see module docstring).

    One broker per *session* (a RAQO instance, a multi-tenant batch of
    queries, a sharding-planner fleet): the backend's compiled programs,
    the session memo, and the dedup scope all live here.
    """

    MAX_MEMO = 4096                    # FIFO bound on the session memo

    def __init__(self, backend=None, double_buffer: bool = True):
        self.backend: PlanBackend = get_backend(backend)
        self.double_buffer = bool(double_buffer)
        self._pending: List[Tuple[PlanRequest, PlanFuture]] = []
        self._inflight: Optional[_Wave] = None
        # exact-signature session memo for cache-less callers; callers
        # with a ResourcePlanCache keep the cache as their single source
        # of cross-flush reuse (so mutable-cache semantics stay per-op)
        self._memo: Dict[Tuple, Tuple[BatchCostFn, Result]] = {}
        self._instant = 0              # traced memo hits at submit, unfed
        self.stats = PlanningStats()   # broker-level aggregate

    # ------------------------------------------------------------------ #
    def _key(self, req: PlanRequest) -> Tuple:
        return (id(req.fn), req.cluster.dims, req.params.tobytes(),
                req.mode, req.n_random, req.seed)

    def _bump(self, req: PlanRequest, field: str, n: int = 1) -> None:
        setattr(self.stats, field, getattr(self.stats, field) + n)
        if req.stats is not None:
            setattr(req.stats, field, getattr(req.stats, field) + n)

    def submit(self, req: PlanRequest) -> PlanFuture:
        """Queue a request; returns a future resolved at the next flush
        (or immediately, on a session-memo hit)."""
        fut = PlanFuture(self)
        if _obs.enabled:
            fut.submit_ns = time.perf_counter_ns()
        self._bump(req, "broker_requests")
        if req.cache is None:
            hit = self._memo.get(self._key(req))
            if hit is not None and hit[0] is req.fn:
                self._bump(req, "broker_dedup_hits")
                fut.value, fut.done = hit[1], True
                if fut.submit_ns is not None:
                    # resolved inside submit(): no wave, latency 0, fed
                    # to the histogram in bulk (_observe_instant)
                    fut.verdict = "memo"
                    self._instant += 1
                return fut
        self._pending.append((req, fut))
        return fut

    def pending_count(self) -> int:
        return len(self._pending)

    def _record_wave(self, pending) -> None:
        """Wave accounting: one entry per non-empty flush, sized by the
        requests that entered it (broker-level only — a wave spans many
        costings, so per-request stats never see these counters)."""
        self.stats.broker_waves += 1
        self.stats.broker_wave_sizes.append(len(pending))

    def _observe_instant(self) -> None:
        """Feed ``broker.request_s`` the traced memo hits at submit since
        the last call, at latency 0 (they resolve inside ``submit()``):
        once per flush and per snapshot, so no request pays a histogram
        update of its own."""
        if self._instant:
            _metrics.histogram("broker.request_s").observe_many(
                [0.0] * self._instant)
            self._instant = 0

    def counters_snapshot(self) -> dict:
        """JSON-friendly broker counters including flush-wave geometry —
        the lockstep multi-query win is wave *shape* (few waves, ΣQ_L
        requests each), not just wall-clock, so benches trend these next
        to the timings.  Also brings ``broker.request_s`` up to date."""
        self._observe_instant()
        ws = list(self.stats.broker_wave_sizes)
        return {
            "requests": self.stats.broker_requests,
            "dedup_hits": self.stats.broker_dedup_hits,
            "batches": self.stats.broker_batches,
            "waves": self.stats.broker_waves,
            "wave_sizes": ws,
            "max_wave": max(ws) if ws else 0,
            "mean_wave": round(sum(ws) / len(ws), 3) if ws else 0.0,
        }

    # ------------------------------------------------------------------ #
    @staticmethod
    def _lookup(req: PlanRequest) -> Optional[Result]:
        """One cache lookup + validate for ``req`` (sequential
        semantics); None when it must search."""
        hit = req.cache.lookup(req.cache_key[0], req.cache_key[1],
                               req.cache_key[2], req.cluster, req.stats)
        if hit is None:
            return None
        cfg = tuple(int(v) for v in hit)
        cost = req.commit_fn(cfg)
        if not req.validate_hit or math.isfinite(cost):
            return cfg, cost
        # cached plan invalid under current conditions (degraded
        # cluster, budget): caller falls through to search
        return None

    def flush(self) -> None:
        """Resolve every pending request: dedup -> stacked search ->
        float64 commit -> fan-out (stages 1-3 of the module docstring).
        Any in-flight double-buffered wave commits first, so sequential
        ordering is preserved."""
        self._flush_sync("explicit")

    @hot_path("resolves every pending request of the session per flush")
    def _flush_sync(self, cause: str) -> None:
        """``flush()`` on behalf of ``cause`` (``explicit`` or
        ``result``): the synchronous wave runs under a
        ``broker.flush.sync`` span and counts in
        ``broker.sync_flushes.<cause>``."""
        self._commit_inflight()
        pending, self._pending = self._pending, []
        if not pending:
            return
        self._record_wave(pending)
        wave_no = self.stats.broker_waves
        _metrics.counter("broker.sync_flushes." + cause).inc()
        with _obs.span("broker.flush.sync", cat="broker") as fsp:
            order, execs, stamps = self._stage1_span(pending, wave_no)
            groups = 0
            if execs:
                fin, groups = self._dispatch_span(execs, wave_no, stamps,
                                                  sync=True)
                self._finish(order, execs, fin, wave_no, stamps)
            if fsp:
                fsp.set(cause=cause, requests=len(pending), groups=groups,
                        wave=wave_no)

    def flush_async(self) -> None:
        """Double-buffered flush: commit the previous in-flight wave
        (its programs ran while the caller enumerated), dispatch the
        currently pending requests as the NEW in-flight wave, and return
        without syncing.  Results land at the next ``flush_async()`` /
        ``flush()`` / ``result()`` on one of the wave's futures — always
        committed in submission order before any newer stage-1 lookup,
        so outcomes are bit-identical to calling ``flush()`` at the same
        points (the identity the broker property tests pin)."""
        if not self.double_buffer:
            self.flush()
            return
        self._commit_inflight()
        pending, self._pending = self._pending, []
        if not pending:
            return
        self._record_wave(pending)
        wave_no = self.stats.broker_waves
        order, execs, stamps = self._stage1_span(pending, wave_no)
        if not execs:
            return
        futs = frozenset(id(f) for f in _wave_futures(order))
        fin, _ = self._dispatch_span(execs, wave_no, stamps, sync=False)
        self._inflight = _Wave(order=order, execs=execs, finalize=fin,
                               futs=futs, wave_no=wave_no, stamps=stamps)

    def inflight_count(self) -> int:
        """Futures the in-flight wave will resolve (0 when none)."""
        return 0 if self._inflight is None else len(self._inflight.futs)

    def _commit_inflight(self) -> None:
        """Finalize + commit the in-flight wave, if any (the first step
        of every flush)."""
        self._observe_instant()
        wave, self._inflight = self._inflight, None
        if wave is not None:
            self._finish(wave.order, wave.execs, wave.finalize,
                         wave.wave_no, wave.stamps)

    def _ensure(self, fut: PlanFuture) -> None:
        """Resolve ``fut``: a member of the in-flight wave commits just
        that wave (newer pending requests stay pending, still
        accumulating into the next one); anything else takes the full
        synchronous flush, counted under cause ``result``."""
        if self._inflight is not None and id(fut) in self._inflight.futs:
            self._commit_inflight()
        else:
            self._flush_sync("result")

    def _stage1_span(self, pending, wave_no: int):
        """Stage 1 under the ``broker.stage1`` span; traced, also the
        wave's stamps (else None) and the latencies of the requests it
        resolved."""
        stamps = _Stamps(wave_no) if _obs.enabled else None
        with _obs.span("broker.stage1", cat="broker") as sp:
            order, execs = self._stage1(pending, stamps)
            if sp:
                sp.set(wave=wave_no, size=len(pending), leaders=len(execs))
        if stamps is not None:
            stamps.stage1 = time.perf_counter_ns()
            _observe_latencies(
                [f for _, f in pending if f.done and f.wave is stamps],
                stamps.stage1)
        return order, execs, stamps

    def _dispatch_span(self, execs, wave_no: int, stamps, sync: bool):
        """Stage 2's dispatch under the ``broker.dispatch`` span; traced,
        stamps the wave and opens its async interval (closed at commit,
        so double-buffered waves render as overlapping tracks)."""
        with _obs.span("broker.dispatch", cat="broker") as sp:
            fin, groups = self._dispatch(execs, wave_no=wave_no, sync=sync)
            if sp:
                sp.set(wave=wave_no, groups=groups, leaders=len(execs),
                       pipelined=not sync)
        if stamps is not None:
            stamps.dispatch = time.perf_counter_ns()
            _obs.async_begin("wave", wave_no, leaders=len(execs),
                             pipelined=not sync)
        return fin, groups

    # ------------------------------------------------------------------ #
    def _stage1(self, pending: List[Tuple[PlanRequest, PlanFuture]],
                stamps: Optional[_Stamps] = None
                ) -> Tuple[List[Tuple[str, object]], List[_Exec]]:
        """Stage 1: cache fronting + within-flush dedup.

        Interpolating (nearest-neighbor / weighted-average) caches must
        observe same-flush inserts, so their lookups are deferred to
        stage 3 (submission order); their searches still run stacked in
        stage 2, speculatively.  Exact caches cannot hit on anything a
        same-flush insert adds under a *different* key, so a first-seen
        key's lookup happens here — but a request whose key an EARLIER
        same-flush request already claimed must replay in stage 3: its
        sequential lookup would have seen that leader's fresh insert
        (one miss + one hit, not two misses), which is exactly the
        multi-query lockstep shape where every query's copy of a
        recurring operator lands in one wave.  Cache-less duplicates
        stay plain followers (memo semantics are insertion-order
        identical either way).  Traced (``stamps``), every request gets
        its verdict and the wave's stamps.  Returns (stage-3 submission
        order, leader execs)."""
        leaders: Dict[Tuple, _Exec] = {}
        order: List[Tuple[str, object]] = []   # stage-3 submission order
        traced = stamps is not None
        for req, fut in pending:
            if traced:
                fut.wave = stamps
            cached = req.cache is not None and req.cache_key is not None
            if req.cache is None:
                memo = self._memo.get(self._key(req))
                if memo is not None and memo[0] is req.fn:
                    self._bump(req, "broker_dedup_hits")
                    if traced:
                        fut.verdict = "memo"
                    self._resolve(fut, memo[1])
                    continue
            deferred = cached and \
                getattr(req.cache, "mode", "exact") != "exact"
            if cached:
                dkey = (("cache", id(req.cache)) + req.cache_key +
                        (req.mode, req.n_random, req.seed))
            else:
                dkey = ("exact",) + self._key(req)
            led = leaders.get(dkey)
            if led is not None:
                if traced:
                    fut.verdict = "replay" if cached else "follower"
                if cached:
                    # same cache key as an earlier same-flush request:
                    # the sequential loop would give it a fresh lookup
                    # AFTER the leader's insert (an exact-mode hit / an
                    # interpolating re-interpolation) — full per-request
                    # replay in stage 3, in submission order.  The replay
                    # lookup counts the cache hit sequential planning
                    # would count, so no dedup bump: broker counters stay
                    # sequential-identical under lockstep multi-query
                    order.append(("dfollower", (req, fut)))
                else:
                    self._bump(req, "broker_dedup_hits")
                    led.followers.append((req, fut))
                continue
            if cached and not deferred:
                got = self._lookup(req)
                if got is not None:
                    if traced:
                        fut.verdict = "cache-hit"
                    self._resolve(fut, got)
                    continue
            ex = _Exec(req=req, fut=fut)
            leaders[dkey] = ex
            if traced:
                fut.verdict = "dleader" if deferred else "leader"
            order.append(("dleader" if deferred else "leader", ex))
        return order, list(leaders.values())

    def _finish(self, order: List[Tuple[str, object]], execs: List[_Exec],
                finalize: Callable[[], None], wave_no: int = 0,
                stamps: Optional[_Stamps] = None) -> None:
        """Finalize a dispatched wave (the single host sync), then run
        stage 3: float64 commit + fan-out, in submission order."""
        with _obs.span("broker.wave.execute", cat="broker") as sp:
            finalize()
            if sp:
                sp.set(wave=wave_no)
        if stamps is not None:
            stamps.execute = time.perf_counter_ns()
        retry = [ex for ex in execs
                 if ex.req.scan_fallback and ex.req.mode == "ensemble"
                 and not math.isfinite(ex.cost)]
        if retry:
            # all starts stranded on an infeasible plateau: exhaustive
            # scan, still stacked per (fn, grid) group
            self._run(retry, force_mode="grid", wave_no=wave_no)
        with _obs.span("broker.wave.commit", cat="broker") as sp:
            self._commit_wave(order)
            if sp:
                sp.set(wave=wave_no, entries=len(order))
        if stamps is not None:
            stamps.commit = time.perf_counter_ns()
            _observe_latencies(_wave_futures(order), stamps.commit)
            _obs.async_end("wave", wave_no)

    def _commit_wave(self, order: List[Tuple[str, object]]) -> None:
        """Stage 3: float64 commit + fan-out, in submission order."""
        for role, entry in order:
            if role == "dfollower":
                # sequential per-request replay: its lookup sees every
                # insert made earlier in this loop
                freq, ffut = entry
                self._resolve(ffut, self._solve_one(freq))
                continue
            ex = entry
            req = ex.req
            if role == "dleader":
                # deferred (interpolating-cache) lookup, now that earlier
                # requests of this flush have committed their inserts; a
                # hit discards the speculative stage-2 search
                got = self._lookup(req)
                if got is not None:
                    self._resolve(ex.fut, got)
                    continue
            res, cost = self._commit(req, ex.res, ex.cost)
            ok = res is not None and math.isfinite(cost)
            if req.cache is None:
                while len(self._memo) >= self.MAX_MEMO:
                    self._memo.pop(next(iter(self._memo)))
                self._memo[self._key(req)] = (req.fn, (res, cost))
            self._resolve(ex.fut, (res, cost))
            if not ex.followers:
                continue
            if ok or req.cache is None:
                # follower = sequential cache hit: leader's configuration,
                # its own scalar float64 cost (exact-dedup followers are
                # bit-identical requests, so this recomputes the same
                # number the leader committed)
                for freq, ffut in ex.followers:
                    self._resolve(ffut,
                                  (res, freq.commit_fn(res)) if ok
                                  else (res, cost))
            else:
                # leader infeasible -> nothing was inserted; a sequential
                # loop would have searched each follower itself (possibly
                # feasibly — params differ within a cache key), inserting
                # as it goes.  Rare corner: replay it sequentially.
                for freq, ffut in ex.followers:
                    self._resolve(ffut, self._solve_one(freq))

    # ------------------------------------------------------------------ #
    @hot_path("dispatches one stacked search program per (fn, grid) group")
    def _dispatch(self, execs: List[_Exec],
                  force_mode: Optional[str] = None, *, wave_no: int = 0,
                  sync: bool = True) -> Tuple[Callable[[], None], int]:
        """Stage 2, dispatch half: group leaders per (cost-fn, grid,
        mode), stack their params, and launch every group's array
        program via the backend's async split — ALL groups dispatch
        before any result is read back, so a flush mixing cost surfaces
        (SMJ and BHJ operators, say) overlaps their scans on device.
        Each grid group counts the rows its program sweeps, once, in
        ``broker.sweep_rows.sync`` or ``.async`` (``sync``: dispatched by
        a synchronous flush).  Returns the zero-arg finalize performing
        the host syncs and writing raw (res, cost) back onto each _Exec,
        and the number of groups."""
        groups: Dict[Tuple, List[_Exec]] = {}
        for ex in execs:
            req = ex.req
            mode = force_mode or req.mode
            gkey = (id(req.fn), req.cluster.dims, mode, req.n_random,
                    req.seed, len(req.params))
            groups.setdefault(gkey, []).append(ex)
        be = self.backend
        rows = "broker.sweep_rows.sync" if sync else "broker.sweep_rows.async"
        waves = []
        for gkey, entries in groups.items():
            req0 = entries[0].req
            mode = force_mode or req0.mode
            pm = np.stack([ex.req.params for ex in entries])
            gstats = PlanningStats()
            if mode == "grid":
                _metrics.counter(rows).inc(req0.cluster.grid_size())
            with _obs.span("broker.dispatch.group", cat="broker") as sp:
                if mode == "grid":
                    fin = be.argmin_grid_many_async(
                        req0.fn, req0.cluster, pm, stats=gstats)
                else:
                    fin = be.hill_climb_ensemble_many_async(
                        req0.fn, req0.cluster, pm, stats=gstats,
                        n_random=req0.n_random, seed=req0.seed)
                if sp:
                    sp.set(mode=mode, q=len(entries), wave=wave_no,
                           backend=getattr(be, "name", "?"))
            for ex in entries:
                self._bump(ex.req, "broker_batches")
            self.stats.broker_batches -= len(entries) - 1  # one per group
            waves.append((entries, gstats, fin))

        def finalize() -> None:
            for entries, gstats, fin in waves:
                with _obs.span("broker.group.sync", cat="broker") as sp:
                    results = fin()
                    if sp:
                        sp.set(q=len(entries), wave=wave_no)
                # attribute the group's exploration evenly (grid groups
                # are exactly grid_size per request; climb convergence
                # varies per request, so the split is approximate there)
                share, rem = divmod(gstats.configs_explored, len(entries))
                for i, (ex, rc) in enumerate(zip(entries, results)):
                    ex.res, ex.cost = rc
                    if ex.req.stats is not None:
                        n = share + (rem if i == 0 else 0)
                        ex.req.stats.configs_explored += n
                        ex.req.stats.cost_calls += n
        return finalize, len(groups)

    def _run(self, execs: List[_Exec], force_mode: Optional[str] = None,
             wave_no: int = 0) -> None:
        """Synchronous stage 2: dispatch + immediate finalize (the
        scan_fallback retry path, a synchronous flush of cause
        ``retry``)."""
        _metrics.counter("broker.sync_flushes.retry").inc()
        with _obs.span("broker.flush.sync", cat="broker") as sp:
            fin, groups = self._dispatch(execs, force_mode, wave_no=wave_no,
                                         sync=True)
            fin()
            if sp:
                sp.set(cause="retry", requests=len(execs), groups=groups,
                       wave=wave_no)

    def _commit(self, req: PlanRequest, res, cost: float) -> Result:
        """Float64 commit of one raw search result: re-cost through the
        caller's scalar fn; on a feasibility disagreement, exact backends
        assert parity and non-exact ones redo the search on the float64
        numpy backend; feasible plans are inserted into the cache."""
        if res is not None:
            raw, cost = cost, req.commit_fn(res)
            if not math.isfinite(cost):
                if getattr(self.backend, "exact", False):
                    # exact backend: search and commit compute in the
                    # same float64 arithmetic — feasibility must agree
                    assert not math.isfinite(raw), (
                        f"exact backend {self.backend.name} selected "
                        f"{res} with finite search cost {raw} but "
                        f"infinite float64 commit")
                elif req.fallback_fn is not None:
                    self.stats.broker_researches += 1
                    res, cost = get_backend("numpy").argmin_grid(
                        req.fallback_fn, req.cluster, req.stats,
                        params=req.params)
                    if res is not None:
                        cost = req.commit_fn(res)
        if res is not None and math.isfinite(cost) and \
                req.cache is not None and req.cache_key is not None:
            req.cache.insert(req.cache_key[0], req.cache_key[1],
                             req.cache_key[2], res, stats=req.stats)
        return res, cost

    def _solve_one(self, req: PlanRequest) -> Result:
        """Strictly sequential per-operator semantics for one request:
        lookup -> search -> commit -> insert (the promotion path for
        followers of an infeasible leader)."""
        if req.cache is not None and req.cache_key is not None:
            hit = req.cache.lookup(req.cache_key[0], req.cache_key[1],
                                   req.cache_key[2], req.cluster, req.stats)
            if hit is not None:
                cfg = tuple(int(v) for v in hit)
                cost = req.commit_fn(cfg)
                if not req.validate_hit or math.isfinite(cost):
                    return cfg, cost
        stats = req.stats if req.stats is not None else PlanningStats()
        before = stats.configs_explored
        if req.mode == "grid":
            res, cost = self.backend.argmin_grid(
                req.fn, req.cluster, stats, params=req.params)
        else:
            res, cost = self.backend.hill_climb_ensemble(
                req.fn, req.cluster, stats=stats, params=req.params,
                n_random=req.n_random, seed=req.seed)
            if not math.isfinite(cost) and req.scan_fallback:
                res, cost = self.backend.argmin_grid(
                    req.fn, req.cluster, stats, params=req.params)
        stats.cost_calls += stats.configs_explored - before
        return self._commit(req, res, cost)

    @staticmethod
    def _resolve(fut: PlanFuture, value: Result) -> None:
        fut.value = (None if value[0] is None
                     else tuple(int(v) for v in value[0]), float(value[1]))
        fut.done = True
