"""The plain reference and the comparison, at a tiny size on the CPU."""

import sqlite3

import numpy as np
import pytest

from perfbench.lib import check
from perfbench.lib.bench import Benchmark
from perfbench.lib.corpus import CHUNK_TYPES, PROJECTS, generate
from perfbench.lib.embedding import HashEmbedding
from perfbench.lib.reference import Reference, answers, mmr, sql_order, top_rows

CFG = dict(rows=3_000, sessions=50, now=1_770_000_000.0, days=180,
           seconds_between_chunks=30, cluster_weights=[0.42, 0.13, 0.45],
           chunk_type_weights=[0.2, 0.45, 0.25, 0.1])
SUP = "website landing page"


@pytest.fixture(scope="module")
def emb():
    return HashEmbedding(128)


@pytest.fixture(scope="module")
def corpus(emb):
    return generate(CFG, 424242, emb)


@pytest.fixture(scope="module")
def ref(corpus, emb):
    return Reference(corpus, emb)


def spec(**kw):
    base = {"kind": "t", "surface": "search", "k": 10, "similar": "server lifecycle restart",
            "suppress": [], "from": None, "to": None, "decay": None, "diverse": False,
            "pool": None, "filter": None, "hybrid": None, "keyword": None}
    base.update(kw)
    return base


def u(v):
    v = np.asarray(v, np.float64)
    return v / np.linalg.norm(v)


def test_scores_follow_the_paper_order(ref, corpus, emb):
    sp = spec(suppress=[SUP], **{"from": "prototype sketch", "to": "production deploy"},
              decay=30.0)
    (sc,) = ref.score([sp])
    m = corpus.matrix.astype(np.float64)
    q, a, b, x = (u(emb(t)) for t in (sp["similar"], "prototype sketch",
                                       "production deploy", SUP))
    days = np.maximum((corpus.now - corpus.timestamps) / 86400.0, 0)
    want = (0.5 * (m @ q) + 0.5 * (m @ (b - a))) / (1 + days / 30.0) - 0.5 * (m @ x)
    np.testing.assert_allclose(sc.scores, want, atol=1e-12)


def test_filter_is_hard(ref, corpus):
    f = {"type": "file", "project": "core"}
    sp = spec(filter=f, k=50)
    (sc,) = ref.score([sp])
    ans = ref.answer(sp, sc)
    elig = (corpus.ctype == CHUNK_TYPES.index("file")) & (corpus.project == PROJECTS.index("core"))
    assert sc.n_eligible == int(elig.sum()) and len(ans) == min(50, sc.n_eligible)
    assert all(elig[i] for i, _ in ans)


def test_diverse_is_greedy_mmr_over_the_oversampled_pool(ref):
    sp = spec(diverse=True, decay=14.0, k=10)
    (sc,) = ref.score([sp])
    ans = ref.answer(sp, sc)
    pool = top_rows(sc.scores, 1500)
    e = ref.rows(pool)
    picks, chosen = [], []
    for _ in range(10):
        best, arg = -np.inf, None
        for j in range(pool.size):
            if j in chosen:
                continue
            pen = max((e[j] @ e[c] for c in chosen), default=0.0)
            o = 0.7 * sc.scores[pool[j]] - 0.3 * pen
            if o > best:
                best, arg = o, j
        chosen.append(arg)
        picks.append(int(pool[arg]))
    assert [i for i, _ in ans] == picks
    assert ans[0][0] == int(pool[0])


def test_bm25_matches_sqlite_fts5(corpus, ref):
    conn = sqlite3.connect(":memory:")
    conn.execute("CREATE VIRTUAL TABLE t USING fts5(content)")
    conn.executemany("INSERT INTO t (rowid, content) VALUES (?, ?)",
                     [(i, corpus.content(i)) for i in range(corpus.n)])
    for q in ["server lifecycle", "database schema index", "website", "zzzz"]:
        rows = conn.execute("SELECT rowid, -bm25(t) FROM t WHERE t MATCH ?", (q,)).fetchall()
        ids, sc = ref.bm25(q.split())
        got = dict(zip(ids.tolist(), sc.tolist()))
        assert set(got) == {r for r, _ in rows}
        for r, v in rows:
            assert got[r] == pytest.approx(v, rel=1e-9, abs=1e-9)


def test_weighted_fusion(ref):
    sp = spec(surface="sql", k=None, hybrid=0.6, keyword="server lifecycle",
              similar="server lifecycle")
    (sc,) = ref.score([sp])
    (plain,) = ref.score([spec(similar="server lifecycle")])
    lex = ref.lexical("server lifecycle", 500)
    assert lex.max() == 1.0 and (lex > 0).sum() <= 500
    np.testing.assert_allclose(sc.scores, 0.6 * plain.scores + 0.4 * lex, atol=1e-12)
    ans = ref.answer(sp, sc)
    assert len(ans) == 500 and ans[0][1] == 1.0 and ans[-1][1] == 0.0


def test_regret_reads_near_ties_small_and_wrong_rows_large():
    s = np.array([0.9, 0.5, 0.5 - 1e-9, 0.1, -np.inf])
    assert check.ranking_regret(s, [0, 1, 2]) == 0.0
    assert check.ranking_regret(s, [0, 2, 1]) == pytest.approx(1e-9)
    assert check.ranking_regret(s, [0, 3]) == pytest.approx(0.4)
    assert check.ranking_regret(s, [0, 0]) == float("inf")
    assert check.ranking_regret(s, [4]) == float("inf")


def _program_answers(corpus, emb, specs):
    """The program's direct jit-jax path on the CPU, for the tiny size."""
    from repro.core.vectorcache import VectorCache

    vc = VectorCache(corpus.ids, corpus.matrix, corpus.timestamps, emb, normalized=True)
    return [vc.search(s["text"], now=corpus.now, engine="jit-jax")[: s["k"]] for s in specs]


@pytest.fixture(scope="module")
def search_specs():
    bench = Benchmark()
    rng = np.random.default_rng(5)
    out = []
    for name, repeat in (("search_open", 3), ("search_single", 1)):
        traffic = bench.traffic(name)
        req = bench.requests(traffic)
        out += [req.make(rng, e, traffic) for e in traffic["mix"] for _ in range(repeat)]
    return out


def test_program_passes_and_bf16_control_fails(corpus, emb, ref, search_specs):
    limits = Benchmark().limits("h1m_search_closed64")
    got = _program_answers(corpus, emb, search_specs)
    program = check.compare_all(ref, search_specs, got)
    assert check.verdict(program, limits), program
    ctrl = answers(Reference(corpus, emb, precision="bf16"), search_specs)
    control = check.compare_all(ref, search_specs, [ctrl[j] for j in range(len(search_specs))])
    assert not check.verdict(control, limits), control


def test_sql_answer_is_normalised_and_ordered():
    out = sql_order([(7, 0.2), (3, 0.6), (5, 0.2), (1, 0.4)])
    assert [i for i, _ in out] == [3, 1, 5, 7]
    np.testing.assert_allclose([v for _, v in out], [1.0, 0.5, 0.0, 0.0], atol=1e-12)


def test_mmr_positions():
    e = np.eye(3)
    assert list(mmr(e, np.array([0.9, 0.8, 0.1]), 3, 0.7)) == [0, 1, 2]
