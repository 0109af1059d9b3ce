"""Async continuous-batching engine: admission, deadlines, pipeline, drain.

Deterministic control comes from a gate backend (``score_select`` blocks
until the test releases it), so queue states are pinned exactly — no
sleep-and-hope.  The pipeline-overlap test uses sleeps INSIDE the two
stages (pure waiting, not CPU), so the wall-clock comparison is a
scheduling property, robust on loaded CI runners.
"""

import asyncio
import concurrent.futures as cf
import sqlite3
import threading
import time

import numpy as np
import pytest

import repro.serve.engine as engine_mod
from repro.core.backends import FusedNumpyBackend
from repro.core.segments import CompactionPolicy, SegmentedCorpusStore
from repro.core.vectorcache import VectorCache
from repro.data.corpus import build_database, generate_corpus
from repro.embed import HashEmbedder
from repro.serve.engine import (BatchedRetrievalEngine, DeadlineExceededError,
                                EngineClosedError, QueueFullError, Request)
from repro.serve.retrieval import RetrievalService

NOW = 90 * 86400.0

# captured ONCE at import: _run_staged patches this name per engine run,
# and grabbing it inside the helper would wrap the previous run's wrapper
_ORIG_TAIL = engine_mod.finalize_segment_candidates


class GateBackend(FusedNumpyBackend):
    """Backend whose scoring pass blocks until the test releases it (and
    optionally sleeps, to give the device stage a controllable duration)."""

    name = "gate"

    def __init__(self, *, released: bool = False, delay_s: float = 0.0):
        self.release = threading.Event()
        if released:
            self.release.set()
        self.entered = threading.Event()
        self.delay_s = delay_s
        self.calls = 0

    def score_select(self, *args, **kwargs):
        self.calls += 1
        self.entered.set()
        if self.delay_s:
            time.sleep(self.delay_s)
        if not self.release.wait(timeout=15.0):
            raise RuntimeError("gate backend never released (test bug)")
        return super().score_select(*args, **kwargs)


def make_cache(n=200, dim=32):
    emb = HashEmbedder(dim)
    texts = [f"item group {i % 7} tail {i}" for i in range(n)]
    return VectorCache(np.arange(n), emb.embed_batch(texts),
                       np.linspace(0, 89 * 86400, n), emb), emb


def wait_for(predicate, timeout=10.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# admission: backpressure + bounded queue
# ---------------------------------------------------------------------------


def test_backpressure_rejects_at_capacity():
    cache, _ = make_cache()
    gate = GateBackend()
    eng = BatchedRetrievalEngine(cache, max_batch=1, engine=gate, max_queue=2)
    try:
        with cf.ThreadPoolExecutor(4) as ex:
            first = ex.submit(eng.search, "similar:group 1 tail", 5)
            assert gate.entered.wait(5.0)  # first request is IN the device pass
            queued = [ex.submit(eng.search, f"similar:group {i} tail", 5)
                      for i in (2, 3)]
            assert wait_for(lambda: eng.queue_depth == 2)
            with pytest.raises(QueueFullError):
                eng.search("similar:group 4 tail", 5, timeout=5.0)
            assert eng.rejected == 1
            gate.release.set()
            assert len(first.result(10.0)) == 5
            for f in queued:
                assert len(f.result(10.0)) == 5
        assert eng.queue_depth == 0
        assert eng.stats()["rejected"] == 1
    finally:
        gate.release.set()
        eng.close()


# ---------------------------------------------------------------------------
# deadlines + priorities at collect time
# ---------------------------------------------------------------------------


def test_deadline_miss_fails_at_collect():
    cache, _ = make_cache()
    gate = GateBackend()
    eng = BatchedRetrievalEngine(cache, max_batch=1, engine=gate)
    try:
        with cf.ThreadPoolExecutor(2) as ex:
            blocker = ex.submit(eng.search, "similar:group 1 tail", 5)
            assert gate.entered.wait(5.0)
            doomed = ex.submit(eng.search, "similar:group 2 tail", 5,
                               10.0, deadline_ms=20.0)
            assert wait_for(lambda: eng.queue_depth == 1)
            time.sleep(0.1)  # let the 20 ms deadline lapse while queued
            gate.release.set()
            assert len(blocker.result(10.0)) == 5
            with pytest.raises(DeadlineExceededError):
                doomed.result(10.0)
        assert eng.deadline_misses == 1
    finally:
        gate.release.set()
        eng.close()


def test_priority_orders_collect():
    cache, _ = make_cache()
    # one-permit-per-batch gate: a one-shot release would let every batch
    # through at once, and with sub-ms batches the "which search() call
    # returned first" observation races worker-thread wakeups — stepping
    # batch by batch makes the serving order directly observable
    sem = threading.Semaphore(0)

    class StepGate(GateBackend):
        def score_select(self, *args, **kwargs):
            self.calls += 1
            self.entered.set()
            if not sem.acquire(timeout=15.0):
                raise RuntimeError("gate backend never released (test bug)")
            return FusedNumpyBackend.score_select(self, *args, **kwargs)

    gate = StepGate()
    eng = BatchedRetrievalEngine(cache, max_batch=1, engine=gate)
    order = []
    try:
        with cf.ThreadPoolExecutor(4) as ex:
            blocker = ex.submit(eng.search, "similar:group 1 tail", 5)
            assert gate.entered.wait(5.0)

            def tagged(tokens, tag, priority):
                eng.search(tokens, 5, priority=priority)
                order.append(tag)

            low = ex.submit(tagged, "similar:group 2 tail", "low", 0)
            assert wait_for(lambda: eng.queue_depth == 1)
            high = ex.submit(tagged, "similar:group 3 tail", "high", 5)
            assert wait_for(lambda: eng.queue_depth == 2)
            sem.release()                    # serve the blocker batch
            blocker.result(10.0)
            sem.release()                    # serve ONE queued request...
            assert wait_for(lambda: len(order) == 1)  # ...observe its return
            sem.release()                    # then the other
            high.result(10.0)
            low.result(10.0)
        # max_batch=1: the two queued requests served one per batch,
        # highest priority first despite arriving second
        assert order == ["high", "low"]
    finally:
        sem.release()
        sem.release()
        sem.release()
        eng.close()


# ---------------------------------------------------------------------------
# close() drains the queue (no 30 s hang)
# ---------------------------------------------------------------------------


def test_close_drains_pending_requests():
    cache, _ = make_cache()
    gate = GateBackend()
    eng = BatchedRetrievalEngine(cache, max_batch=1, engine=gate)
    with cf.ThreadPoolExecutor(4) as ex:
        in_flight = ex.submit(eng.search, "similar:group 1 tail", 5)
        assert gate.entered.wait(5.0)
        queued = [ex.submit(eng.search, f"similar:group {i} tail", 5)
                  for i in (2, 3)]
        assert wait_for(lambda: eng.queue_depth == 2)
        t0 = time.monotonic()
        closer = ex.submit(eng.close)
        time.sleep(0.05)
        gate.release.set()
        closer.result(10.0)
        # in-flight batch completes; everything queued fails FAST with a
        # clear shutdown error instead of hanging into its 30 s timeout
        assert len(in_flight.result(10.0)) == 5
        for f in queued:
            with pytest.raises(EngineClosedError):
                f.result(10.0)
        assert time.monotonic() - t0 < 10.0
    with pytest.raises(EngineClosedError):
        eng.search("similar:anything", 3)


# ---------------------------------------------------------------------------
# monotonic latency accounting
# ---------------------------------------------------------------------------


def test_latency_clock_is_monotonic_not_wall():
    # time.time() is ~1.7e9 s; time.perf_counter() (monotonic, and the
    # span recorder's clock) is boot-relative.  If someone reverts
    # enqueued_at to wall clock, this pins it.
    req = Request(tokens="similar:x")
    assert abs(req.enqueued_at - time.perf_counter()) < 60.0
    cache, _ = make_cache()
    eng = BatchedRetrievalEngine(cache, engine="fused")
    try:
        req2 = Request(tokens="similar:group 1 tail", k=3)
        eng._submit(req2)
        req2.future.result(10.0)
        assert 0.0 <= req2.latency_ms < 60_000.0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# async facade + equivalence
# ---------------------------------------------------------------------------


def test_asearch_matches_direct_path():
    cache, _ = make_cache(300)
    eng = BatchedRetrievalEngine(cache, max_batch=16, now=NOW, engine="fused")
    tokens = [f"similar:group {i % 7} tail decay:14" for i in range(20)]
    try:
        async def main():
            return await asyncio.gather(
                *[eng.asearch(t, 5) for t in tokens])

        batched = asyncio.run(main())
        direct = [cache.search(t, now=NOW)[:5] for t in tokens]
        # rankings bit-identical; scores to fp tolerance (the (d, B) panel
        # matmul and the single-query matvec reassociate differently)
        for b, d in zip(batched, direct):
            assert [i for i, _ in b] == [i for i, _ in d]
            np.testing.assert_allclose([v for _, v in b],
                                       [v for _, v in d], rtol=1e-5)
        assert eng.batches_served < len(tokens)  # batching actually batched
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# the pipeline: overlap counter + wall-clock win
# ---------------------------------------------------------------------------


def _run_staged(monkeypatch, *, pipeline: bool, n_requests: int = 8,
                stage_s: float = 0.03):
    """Serve n_requests with both stages stubbed to sleep ``stage_s``
    (sleeps release the GIL and cost no CPU, so the comparison measures
    SCHEDULING, not machine load)."""
    cache, _ = make_cache(50)
    gate = GateBackend(released=True, delay_s=stage_s)

    def slow_tail(*args, **kwargs):
        time.sleep(stage_s)
        return _ORIG_TAIL(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "finalize_segment_candidates", slow_tail)
    eng = BatchedRetrievalEngine(cache, max_batch=1, max_wait_ms=0.5,
                                 engine=gate, pipeline=pipeline)
    try:
        t0 = time.monotonic()
        with cf.ThreadPoolExecutor(n_requests) as ex:
            futs = [ex.submit(eng.search, f"similar:group {i % 7} tail", 3)
                    for i in range(n_requests)]
            for f in futs:
                assert len(f.result(30.0)) == 3
        wall = time.monotonic() - t0
        return wall, eng.overlapped_batches
    finally:
        eng.close()


def test_pipeline_overlaps_and_beats_sync_core(monkeypatch):
    wall_sync, overlap_sync = _run_staged(monkeypatch, pipeline=False)
    wall_pipe, overlap_pipe = _run_staged(monkeypatch, pipeline=True)
    # sync core serializes device+tail (~2*stage per batch); the pipeline
    # overlaps tail i with device pass i+1 (~1*stage per batch in steady
    # state).  Generous margin: pipelined must be at least 20% faster.
    assert overlap_sync == 0
    assert overlap_pipe > 0
    assert wall_pipe < wall_sync * 0.8, (wall_pipe, wall_sync)


# ---------------------------------------------------------------------------
# background compaction: idle gaps only, never inside a scoring pass
# ---------------------------------------------------------------------------


def test_compaction_policy_picks_victims():
    store = SegmentedCorpusStore(dim=4)
    rng = np.random.default_rng(0)
    for s in range(6):
        store.append(np.arange(s * 10, s * 10 + 10),
                     rng.standard_normal((10, 4)).astype(np.float32))
    # liveness pressure: tombstone 6/10 of segment 0
    store.delete(list(range(6)))
    pol = CompactionPolicy(min_live_fraction=0.5, max_segments=10)
    assert pol.should_compact(store)
    assert store.maybe_compact(pol) == 1          # folds the sparse segment
    assert store.n_segments == 6                  # 5 survivors + 1 merged
    assert not pol.should_compact(store)
    # count pressure: cap at 3 segments -> the smallest fold together
    pol2 = CompactionPolicy(min_live_fraction=0.1, max_segments=3)
    assert pol2.should_compact(store)
    assert store.maybe_compact(pol2) >= 3
    assert store.n_segments <= 3
    assert store.n_live == 54                     # no live row lost
    assert store.maybe_compact(pol2) == 0         # converged, no churn


def test_idle_compaction_never_inside_scoring_pass(monkeypatch):
    cache, _ = make_cache(300)
    store = cache.store
    windows = {"score": [], "fold": []}

    orig_sss = engine_mod.score_select_segments

    def recording_sss(*args, **kwargs):
        t0 = time.monotonic()
        out = orig_sss(*args, **kwargs)
        windows["score"].append((t0, time.monotonic()))
        return out

    monkeypatch.setattr(engine_mod, "score_select_segments", recording_sss)

    orig_fold = SegmentedCorpusStore._fold

    def recording_fold(self, victims):
        t0 = time.monotonic()
        out = orig_fold(self, victims)
        if out:
            windows["fold"].append((t0, time.monotonic()))
        return out

    monkeypatch.setattr(SegmentedCorpusStore, "_fold", recording_fold)

    pol = CompactionPolicy(min_live_fraction=0.9, max_segments=4)
    eng = BatchedRetrievalEngine(cache, max_batch=8, now=NOW, engine="fused",
                                 compaction=pol)
    emb = HashEmbedder(32)
    try:
        stop = threading.Event()

        def searcher(seed):
            i = seed
            while not stop.is_set():
                eng.search(f"similar:group {i % 7} tail decay:14", 5)
                i += 1

        threads = [threading.Thread(target=searcher, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        # fragment the store while queries race: appends + deletes
        next_id = 10_000
        rng = np.random.default_rng(1)
        for cycle in range(8):
            ids = np.arange(next_id, next_id + 12)
            next_id += 12
            eng.ingest(ids, rng.standard_normal((12, 32)).astype(np.float32),
                       np.full(12, NOW - 1000.0))
            eng.delete(ids[:8].tolist())
            time.sleep(0.02)
        stop.set()
        for t in threads:
            t.join(10.0)
        # idle gap: the scheduler should now run the compaction policy
        assert wait_for(lambda: eng.compactions_run >= 1, timeout=10.0)
        assert store.compactions >= 1
    finally:
        eng.close()

    assert windows["fold"], "compaction never ran"
    for fs, fe in windows["fold"]:
        for ss, se in windows["score"]:
            assert fe <= ss or se <= fs, (
                f"compaction [{fs:.4f},{fe:.4f}] landed inside scoring "
                f"pass [{ss:.4f},{se:.4f}]")


# ---------------------------------------------------------------------------
# concurrent ingest/delete racing the scheduler
# ---------------------------------------------------------------------------


def test_concurrent_mutations_stay_bit_identical():
    cache, _ = make_cache(250)
    eng = BatchedRetrievalEngine(
        cache, max_batch=8, now=NOW, engine="fused",
        compaction=CompactionPolicy(min_live_fraction=0.6, max_segments=5))
    tokens = [f"similar:group {i} tail decay:14" for i in range(7)]
    tokens.append("similar:group 2 tail diverse decay:14")
    errors = []
    try:
        stop = threading.Event()

        def searcher(seed):
            i = seed
            while not stop.is_set():
                try:
                    out = eng.search(tokens[i % len(tokens)], 5)
                    assert out, "search returned empty on a live corpus"
                except Exception as e:  # pragma: no cover - failure path
                    errors.append(e)
                    return
                i += 1

        threads = [threading.Thread(target=searcher, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()

        # mutate in bursts; between bursts (mutations quiesced, searches
        # still racing) batched rankings must be bit-identical to the
        # direct VectorCache path on the SAME store state
        rng = np.random.default_rng(7)
        next_id = 50_000
        for burst in range(5):
            ids = np.arange(next_id, next_id + 30)
            next_id += 30
            eng.ingest(ids,
                       rng.standard_normal((30, 32)).astype(np.float32),
                       np.linspace(0, 80 * 86400, 30))
            eng.delete(rng.choice(ids, size=10, replace=False).tolist())
            time.sleep(0.01)
            for t_q in tokens:
                batched = eng.search(t_q, 5)
                direct = cache.search(t_q, now=NOW)[:5]
                assert ([i for i, _ in batched] == [i for i, _ in direct]
                        ), (burst, t_q, batched, direct)
                np.testing.assert_allclose([v for _, v in batched],
                                           [v for _, v in direct],
                                           rtol=1e-5)
        stop.set()
        for t in threads:
            t.join(10.0)
        assert not errors, errors
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# service surface: async entry points + serving stats
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def async_service():
    emb = HashEmbedder(64)
    chunks = generate_corpus(n_chunks=300, n_sessions=20, seed=5)
    conn = sqlite3.connect(":memory:", check_same_thread=False)
    build_database(conn, chunks, emb)
    svc = RetrievalService(conn, dim=64, embedder=emb, now=1_770_000_000.0)
    yield svc
    svc.close()


def test_service_async_surface(async_service):
    svc = async_service

    async def main():
        res = await svc.flex_search_async(
            "SELECT v.id FROM vec_ops('similar:server pool:5') v LIMIT 3")
        assert res.ok, res.error
        hits = await svc.search_async("similar:server lifecycle decay:30", 5)
        assert len(hits) == 5
        row = (9001, "s1", "user", "fresh doc text", 1_769_000_000.0, 0,
               "proj", None, None, None)
        assert await svc.ingest_async([row]) == 1
        hit_ids = [i for i, _ in
                   await svc.search_async("similar:fresh doc text", 3)]
        assert 9001 in hit_ids
        assert await svc.delete_async([9001]) == 1
        return svc.stats()

    stats = asyncio.run(main())
    serving = stats["serving"]
    assert serving["requests_served"] >= 2
    assert serving["queue_depth"] == 0
    for key in ("rejected", "deadline_misses", "overlapped_batches",
                "compactions_run", "max_queue", "batches_served"):
        assert key in serving


# ---------------------------------------------------------------------------
# async dispatch: device future + held admission window
# ---------------------------------------------------------------------------


def test_async_dispatch_holds_window_on_busy_device():
    """While a device pass is in flight, the admission window stays open:
    arrivals fold into ONE next cohort instead of fragmenting into queued
    micro-batches behind the busy executor."""
    cache, _ = make_cache()
    gate = GateBackend()
    eng = BatchedRetrievalEngine(cache, max_batch=4, engine=gate)
    try:
        assert eng.async_dispatch
        with cf.ThreadPoolExecutor(4) as ex:
            first = ex.submit(eng.search, "similar:group 1 tail", 5)
            assert gate.entered.wait(5.0)  # batch 1 is IN the device pass
            held = [ex.submit(eng.search, f"similar:group {i} tail", 5)
                    for i in (2, 3)]
            assert wait_for(lambda: eng.queue_depth == 2)
            # the scheduler reaches the busy-device hold (device still
            # gated, held arrivals pending) before we let the pass finish
            assert wait_for(lambda: eng.overlapped_collects >= 1)
            gate.release.set()
            assert len(first.result(10.0)) == 5
            for f in held:
                assert len(f.result(10.0)) == 5
        assert eng.overlapped_collects >= 1
        assert eng.batches_served == 2  # the two held requests = one cohort
    finally:
        gate.release.set()
        eng.close()


def test_async_dispatch_off_matches_on_and_direct():
    cache, _ = make_cache(300)
    tokens = [f"similar:group {i % 7} tail decay:14" for i in range(16)]
    res = {}
    for mode in (True, False):
        eng = BatchedRetrievalEngine(cache, max_batch=8, now=NOW,
                                     engine="fused", async_dispatch=mode)
        try:
            with cf.ThreadPoolExecutor(8) as ex:
                res[mode] = list(ex.map(lambda t: eng.search(t, 5), tokens))
        finally:
            eng.close()
    direct = [cache.search(t, now=NOW)[:5] for t in tokens]
    for a, b, d in zip(res[True], res[False], direct):
        assert ([i for i, _ in a] == [i for i, _ in b]
                == [i for i, _ in d])


def test_async_dispatch_failures_stay_per_batch():
    """A backend failure under async dispatch fails ITS batch through the
    completion chain; the engine keeps serving."""
    cache, _ = make_cache()

    class FlakyBackend(FusedNumpyBackend):
        name = "flaky"
        boom = True

        def score_select(self, *args, **kwargs):
            if FlakyBackend.boom:
                FlakyBackend.boom = False
                raise RuntimeError("injected device failure")
            return super().score_select(*args, **kwargs)

    eng = BatchedRetrievalEngine(cache, max_batch=4, engine=FlakyBackend())
    try:
        with pytest.raises(RuntimeError, match="injected"):
            eng.search("similar:group 1 tail", 5, timeout=10.0)
        assert len(eng.search("similar:group 2 tail", 5, timeout=10.0)) == 5
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# adaptive batch window
# ---------------------------------------------------------------------------


def test_adaptive_window_learns_gap_and_reports():
    cache, _ = make_cache()
    eng = BatchedRetrievalEngine(cache, max_batch=64, max_wait_ms=2.0,
                                 engine="fused")
    try:
        with cf.ThreadPoolExecutor(8) as ex:
            futs = [ex.submit(eng.search, f"similar:group {i % 7} tail", 3)
                    for i in range(24)]
            for f in futs:
                assert len(f.result(10.0)) == 3
        st = eng.stats()
        assert st["adaptive_window"] is True
        # learned quiescence gap: clamped to [0.05 ms, 4x base]
        assert 0.05 <= st["window_ms"] <= 8.0
        for key in ("overlapped_collects", "windows_extended",
                    "async_dispatch"):
            assert key in st
    finally:
        eng.close()


def test_fixed_window_mode_reports_base():
    cache, _ = make_cache()
    eng = BatchedRetrievalEngine(cache, max_wait_ms=3.0, engine="fused",
                                 adaptive_window=False)
    try:
        st = eng.stats()
        assert st["adaptive_window"] is False
        assert st["window_ms"] == 3.0
        assert len(eng.search("similar:group 1 tail", 5)) == 5
        assert eng.windows_extended == 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# burst starts: a lone client's own round trip is not a cadence
# ---------------------------------------------------------------------------


class SlowFused(FusedNumpyBackend):
    """A fused pass that takes ~7.5 ms, so a lone request's round trip at
    the 2 ms base window lands inside the 8·base cadence cut-off (16 ms):
    a chip-like request time on the CPU."""

    name = "slow-fused"

    def score_select(self, *args, **kwargs):
        time.sleep(0.0075)
        return super().score_select(*args, **kwargs)


def _sequential(eng, n=10):
    for i in range(n):
        assert len(eng.search(f"similar:group {i % 7} tail", 3,
                              timeout=10.0)) == 3


def test_lone_client_window_stays_at_base():
    cache, _ = make_cache()
    eng = BatchedRetrievalEngine(cache, max_wait_ms=2.0, engine="fused")
    try:
        _sequential(eng)
        st = eng.stats()
        # every arrival found its predecessor delivered: no cadence sample
        assert st["burst_starts"] == 10
        assert st["window_ms"] == 2.0
        assert len(eng._undelivered) == 0
    finally:
        eng.close()


def test_lone_client_round_trip_does_not_freeze_window():
    """The regression: a lone request's own latency (8–16 ms here) used to
    be folded in as the cadence, widening every later window to its
    8 ms cap; later round trips past 16 ms were dropped as new bursts,
    so the window never came back."""
    cache, _ = make_cache()
    eng = BatchedRetrievalEngine(cache, max_wait_ms=2.0, engine=SlowFused())
    try:
        _sequential(eng)
        st = eng.stats()
        assert st["window_ms"] == 2.0
        assert st["burst_starts"] == 10
        assert eng.batches_served == 10
    finally:
        eng.close()


def _gated_burst(eng, gate, n):
    """One request in the gated device pass, then ``n`` concurrent
    arrivals while it is undelivered; returns every answer."""
    with cf.ThreadPoolExecutor(n + 1) as ex:
        first = ex.submit(eng.search, "similar:group 0 tail", 3)
        assert gate.entered.wait(5.0)
        rest = [ex.submit(eng.search, f"similar:group {i % 7} tail", 3)
                for i in range(1, n + 1)]
        # every arrival reached the scheduler (not only admission) while
        # the first is still undelivered
        assert wait_for(lambda: len(eng._pending) == n)
        assert len(eng._undelivered) == n + 1
        gate.release.set()
        return [f.result(10.0) for f in [first] + rest]


def test_concurrent_burst_learns_gap_then_lone_search_resets():
    cache, _ = make_cache()
    gate = GateBackend()
    eng = BatchedRetrievalEngine(cache, max_batch=64, max_wait_ms=2.0,
                                 engine=gate)
    try:
        assert all(len(r) == 3 for r in _gated_burst(eng, gate, 7))
        # arrivals 2..8 found an undelivered predecessor: cadence samples
        assert eng._gap_ms is not None
        assert eng.burst_starts == 1
        assert len(eng._undelivered) == 0
        assert len(eng.search("similar:group 3 tail", 3)) == 3
        st = eng.stats()
        assert st["burst_starts"] == 2
        assert st["window_ms"] == 2.0
    finally:
        gate.release.set()
        eng.close()


def test_concurrent_burst_still_folds_into_cohorts():
    cache, _ = make_cache()
    gate = GateBackend()
    eng = BatchedRetrievalEngine(cache, max_batch=64, max_wait_ms=2.0,
                                 engine=gate)
    try:
        assert all(len(r) == 3 for r in _gated_burst(eng, gate, 16))
        st = eng.stats()
        assert st["requests_served"] == 17
        assert st["requests_served"] > st["batches_served"]
        assert st["burst_starts"] == 1
        assert eng._gap_ms is not None
        assert 0.05 <= st["window_ms"] <= 8.0
    finally:
        gate.release.set()
        eng.close()


@pytest.mark.parametrize("path", ["rejected", "shed", "expired",
                                  "admission_error", "backend_error"])
def test_undelivered_returns_to_zero(path):
    cache, _ = make_cache()

    class OnceFailing(GateBackend):
        boom = path == "backend_error"

        def score_select(self, *args, **kwargs):
            if OnceFailing.boom:
                OnceFailing.boom = False
                raise RuntimeError("injected device failure")
            return super().score_select(*args, **kwargs)

    gate = OnceFailing(released=path == "backend_error")
    eng = BatchedRetrievalEngine(cache, max_batch=1, engine=gate, max_queue=2)
    try:
        if path == "backend_error":
            with pytest.raises(RuntimeError, match="injected"):
                eng.search("similar:group 1 tail", 5, timeout=10.0)
            assert len(eng._undelivered) == 0
            assert len(eng.search("similar:group 2 tail", 5)) == 5
        elif path == "admission_error":
            with pytest.raises(Exception):
                eng.search("decay:zzz", 5)
            assert len(eng._undelivered) == 0
            assert eng.queue_depth == 0
        else:
            with cf.ThreadPoolExecutor(4) as ex:
                blocker = ex.submit(eng.search, "similar:group 1 tail", 5)
                assert gate.entered.wait(5.0)
                kw = {"deadline_ms": 20.0} if path == "expired" else {}
                queued = []
                for i in (2, 3):  # one at a time: seq order is shed order
                    queued.append(ex.submit(eng.search,
                                            f"similar:group {i} tail", 5,
                                            10.0, **kw))
                    assert wait_for(lambda: eng.queue_depth == i - 1)
                assert len(eng._undelivered) == 3
                if path == "rejected":
                    with pytest.raises(QueueFullError):
                        eng.search("similar:group 4 tail", 5)
                elif path == "shed":
                    high = ex.submit(eng.search, "similar:group 4 tail", 5,
                                     priority=5)
                    with pytest.raises(QueueFullError):
                        queued[-1].result(10.0)  # newest of the lowest
                    queued = queued[:-1] + [high]
                else:
                    time.sleep(0.1)  # let the 20 ms deadlines lapse
                gate.release.set()
                assert len(blocker.result(10.0)) == 5
                for f in queued:
                    if path == "expired":
                        with pytest.raises(DeadlineExceededError):
                            f.result(10.0)
                    else:
                        assert len(f.result(10.0)) == 5
            counter = {"rejected": "rejected", "shed": "shed_low_priority",
                       "expired": "deadline_misses"}[path]
            assert eng.stats()[counter] >= 1
        assert wait_for(lambda: len(eng._undelivered) == 0)
    finally:
        gate.release.set()
        eng.close()
