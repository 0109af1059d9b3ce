"""The span recorder and the spans of the served path.

The recorder itself (off, nesting, threads, bound), then the layers that
write to it: the batched engine's request and batch spans, the SQL
surface's phase spans, the uploads counter the device span reads, the
compile span, and the stage scopes in the jitted graph's metadata.
"""

import concurrent.futures as cf
import re
import sqlite3
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import (FusedCounters, JitJaxBackend, PlanStructure,
                                 get_backend)
from repro.core.grammar import parse
from repro.core.spans import RECORDER, Recorder
from repro.core.vectorcache import VectorCache
from repro.data.corpus import build_database, generate_corpus
from repro.embed import HashEmbedder
from repro.serve.engine import BatchedRetrievalEngine, Request
from repro.serve.retrieval import RetrievalService

NOW = 90 * 86400.0


@pytest.fixture
def recording():
    """The process recorder, on and empty for one test."""
    RECORDER.drain()
    RECORDER.on = True
    try:
        yield RECORDER
    finally:
        RECORDER.on = False
        RECORDER.drain()


# -- the recorder ------------------------------------------------------------


def test_off_records_nothing():
    rec = Recorder()
    with rec.span("a") as sp:
        assert sp is None
    assert rec.drain() == []


def test_nested_spans_name_their_parent():
    rec = Recorder()
    rec.on = True
    with rec.span("outer", request_id=7):
        with rec.span("inner") as inner:
            inner.attrs["n"] = 3
        with rec.span("sibling"):
            pass
    with rec.span("next"):
        pass
    got = {s.name: s for s in rec.drain()}
    assert got["inner"].parent_id == got["outer"].span_id
    assert got["sibling"].parent_id == got["outer"].span_id
    assert got["outer"].parent_id is None and got["next"].parent_id is None
    assert got["inner"].request_id == 7  # inherited from the parent
    assert got["inner"].attrs == {"n": 3}
    assert got["outer"].start_ns <= got["inner"].start_ns
    assert got["inner"].end_ns <= got["outer"].end_ns
    assert rec.drain() == []


def test_cross_thread_spans_share_the_request_id():
    rec = Recorder()
    rec.on = True
    req = rec.open("request", 42)

    def worker():
        with rec.span("orphan"):  # a thread starts with no current span
            pass
        with rec.span("stage", parent=req):
            pass
        rec.emit("queued", req.start_ns, req.start_ns + 5, parent=req)

    t = threading.Thread(target=worker, name="other")
    t.start()
    t.join()
    rec.close(req)
    got = {s.name: s for s in rec.drain()}
    orphan = got.pop("orphan")
    assert orphan.parent_id is None and orphan.request_id is None
    assert {got[n].request_id for n in got} == {42}
    assert got["stage"].thread == "other" and got["request"].thread != "other"
    assert got["stage"].parent_id == got["queued"].parent_id == req.span_id
    assert got["queued"].end_ns - got["queued"].start_ns == 5


def test_buffer_stays_bounded():
    rec = Recorder(capacity=16)
    rec.on = True
    for i in range(100):
        with rec.span("s", request_id=i):
            pass
    out = rec.drain()
    assert len(out) == 16 and [s.request_id for s in out] == list(range(84, 100))


def test_concurrent_threads_lose_no_span():
    rec = Recorder()
    rec.on = True

    def work(i):
        for j in range(200):
            with rec.span("outer", request_id=i):
                with rec.span("inner"):
                    pass

    with cf.ThreadPoolExecutor(8) as ex:
        list(ex.map(work, range(8)))
    out = rec.drain()
    assert len(out) == 8 * 200 * 2
    assert len({s.span_id for s in out}) == len(out)
    by_id = {s.span_id: s for s in out}
    for s in out:
        if s.name == "inner":
            parent = by_id[s.parent_id]
            assert parent.name == "outer" and parent.request_id == s.request_id
            assert parent.thread == s.thread


# -- the engine --------------------------------------------------------------


def _cache(n=600, dim=32):
    emb = HashEmbedder(dim)
    texts = [f"item group {i % 9} tail {i}" for i in range(n)]
    return VectorCache(np.arange(n), emb.embed_batch(texts),
                       np.linspace(0, 89 * 86400, n), emb)


def test_engine_request_passes_every_layer_in_order(recording):
    eng = BatchedRetrievalEngine(_cache(), max_batch=8, now=NOW,
                                 engine="jit-jax")
    try:
        tokens = [f"similar:group {i % 9} tail decay:14" for i in range(12)]
        tokens += ["similar:group 2 diverse", "similar:group 3 suppress:tail"]
        with cf.ThreadPoolExecutor(6) as ex:
            list(ex.map(lambda t: eng.search(t, 5), tokens))
    finally:
        eng.close()
    out = recording.drain()
    requests = {s.request_id: s for s in out if s.name == "engine.request"}
    assert len(requests) == len(tokens)
    batch = {n: [s for s in out if s.name == n]
             for n in ("engine.collect", "engine.device", "engine.tail")}
    assert all(batch.values())
    for rid, req in requests.items():
        mine = {s.name: s for s in out if s.request_id == rid
                and s.name in ("engine.admit", "engine.queue")}
        assert mine["engine.admit"].parent_id == req.span_id
        assert mine["engine.queue"].parent_id == req.span_id
        dev = [s for s in batch["engine.device"] if rid in s.attrs["requests"]]
        tail = [s for s in batch["engine.tail"] if rid in s.attrs["requests"]]
        col = [s for s in batch["engine.collect"] if rid in s.attrs["requests"]]
        assert len(dev) == len(tail) == len(col) == 1
        chain = [mine["engine.admit"], mine["engine.queue"], dev[0], tail[0]]
        for a, b in zip(chain, chain[1:]):
            assert a.end_ns <= b.start_ns
        assert mine["engine.queue"].end_ns == dev[0].start_ns
        assert req.start_ns <= chain[0].start_ns
        assert chain[-1].start_ns <= req.end_ns <= chain[-1].end_ns
    for dev in batch["engine.device"]:
        assert dev.attrs["arms"] == ["cohort"]
        assert dev.attrs["upload_bytes"] > 0
    for col in batch["engine.collect"]:
        assert 1 <= len(col.attrs["requests"]) <= 8
    served = sorted(r for s in batch["engine.device"] for r in s.attrs["requests"])
    assert served == sorted(requests)


def test_engine_rejected_request_closes_its_span(recording):
    eng = BatchedRetrievalEngine(_cache(), engine="jit-jax", now=NOW)
    try:
        with pytest.raises(Exception):
            eng.search("decay:zzz", 5)
    finally:
        eng.close()
    out = recording.drain()
    (req,) = [s for s in out if s.name == "engine.request"]
    (admit,) = [s for s in out if s.name == "engine.admit"]
    assert req.start_ns <= admit.start_ns <= admit.end_ns == req.end_ns
    assert not [s for s in out if s.name == "engine.queue"]


def test_latency_and_spans_share_one_clock(recording):
    eng = BatchedRetrievalEngine(_cache(), engine="jit-jax", now=NOW)
    try:
        req = Request(tokens="similar:group 4 tail", k=3)
        eng._submit(req)
        req.future.result(30.0)
    finally:
        eng.close()
    (sp,) = [s for s in recording.drain() if s.name == "engine.request"]
    assert req.latency_ms == pytest.approx((sp.end_ns - sp.start_ns) / 1e6,
                                           abs=1e-3)


# -- the SQL surface ---------------------------------------------------------


@pytest.fixture(scope="module")
def service():
    emb = HashEmbedder(64)
    chunks = generate_corpus(n_chunks=500, n_sessions=25, seed=11)
    conn = sqlite3.connect(":memory:", check_same_thread=False)
    build_database(conn, chunks, emb)
    svc = RetrievalService(conn, dim=64, embedder=emb, now=1_770_000_000.0,
                           engine="jit-jax")
    svc.serving(max_batch=4)
    yield svc
    svc.close()


@pytest.mark.parametrize("sql,names", [
    ("SELECT v.id, v.score FROM vec_ops('similar:server lifecycle pool:10', "
     "'SELECT id FROM chunks WHERE type = ''assistant''') v LIMIT 5",
     {"sql.phase1", "sql.plan", "engine.request", "sql.materialize",
      "sql.select"}),
    ("SELECT v.id, v.score FROM hybrid_search('server lifecycle', 0.6) v "
     "LIMIT 5",
     {"sql.plan", "sql.fts", "engine.request", "sql.materialize",
      "sql.select"}),
], ids=["filtered", "hybrid"])
def test_statement_owns_its_phases(service, recording, sql, names):
    res = service.flex_search(sql)
    assert res.ok and res.rows
    out = recording.drain()
    (stmt,) = [s for s in out if s.name == "sql.statement"]
    by_id = {s.span_id: s for s in out}

    def root(s):
        while s.parent_id is not None:
            s = by_id[s.parent_id]
        return s

    seen = {s.name for s in out if root(s) is stmt and s is not stmt}
    assert names <= seen
    (req,) = [s for s in out if s.name == "engine.request"]
    queue = [s for s in out if s.name == "engine.queue"]
    assert queue and queue[0].request_id == req.request_id
    for s in out:
        if s.name.startswith("sql.") and s is not stmt:
            assert stmt.start_ns <= s.start_ns <= s.end_ns <= stmt.end_ns
    assert res.latency_ms == pytest.approx(
        (stmt.end_ns - stmt.start_ns) / 1e6, rel=0.05, abs=0.5)


# -- uploads, compiles, scopes -----------------------------------------------


def test_upload_bytes_count_days_mask_and_panels():
    be = JitJaxBackend()
    rng = np.random.default_rng(0)
    n, d = 1000, 16  # pads to the 1024-row bucket
    mat = rng.standard_normal((n, d)).astype(np.float32)
    days = rng.uniform(0, 30, n)
    emb = HashEmbedder(d)
    plan = parse("similar:alpha suppress:beta decay:7", emb)
    c = FusedCounters()
    be.score_select(mat, days, [plan], [5], counters=c)
    rows = 1024
    corpus = rows * d * 4                       # first call: not yet resident
    panels = 2 * d * 4 + 3 * 4                  # q_pre, q_sup; half, lam, pool_w
    per_call = rows * 4 + rows * 1 + panels + 4  # days f32, live bool, bias dummy
    assert c.upload_bytes == corpus + per_call
    be.score_select(mat, days, [plan], [5], counters=c)
    assert c.upload_bytes == corpus + 2 * per_call
    assert c.stats()["upload_bytes"] == c.upload_bytes


def test_first_call_of_a_built_plan_is_a_compile_span(recording):
    be = JitJaxBackend()
    mat = np.random.default_rng(1).standard_normal((64, 8)).astype(np.float32)
    plan = parse("similar:x", HashEmbedder(8))
    be.score_select(mat, None, [plan], [3])
    be.score_select(mat, None, [plan], [3])
    compiles = [s for s in recording.drain() if s.name == "plan.compile"]
    assert len(compiles) == 1
    assert be.plan_cache.stats()["builds"] == 1


def test_select_graph_names_its_stages():
    st = PlanStructure(batch=2, n_rows=256, has_decay=True, suppress_bucket=1,
                       width=32, mmr_k=8)
    s = jax.ShapeDtypeStruct
    args = [s((256, 16), jnp.float32), s((16, 2), jnp.float32),
            s((16, 2), jnp.float32), s((256,), jnp.float32),
            s((2,), jnp.float32), s((256,), jnp.bool_), s((2,), jnp.float32),
            s((2,), jnp.int32), s((1, 1), jnp.float32)]
    text = JitJaxBackend()._build_select(st).lower(*args).compile().as_text()
    scopes = set(re.findall(r'op_name="jit\(fused_select\)/(\w+)/', text))
    assert {"score", "select", "mmr"} <= scopes
    assert re.search(r'op_name="jit\(fused_select\)/select/top_k"', text)


def test_recorder_off_leaves_the_served_answer_unchanged(recording):
    cache = _cache()
    be = get_backend("jit-jax")
    on = cache.search("similar:group 5 tail diverse", now=NOW, engine=be)
    recording.on = False
    off = cache.search("similar:group 5 tail diverse", now=NOW, engine=be)
    assert on == off
