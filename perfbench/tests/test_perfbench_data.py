"""The benchmark's embedding (held to recorded golden vectors) and the
generated corpus's mix at a small size."""

import json
from pathlib import Path

import numpy as np
import pytest

from perfbench.lib import corpus as C
from perfbench.lib.embedding import HashEmbedding, token_vector

GOLDEN = json.loads((Path(__file__).resolve().parents[1] / "golden" / "embedding.json").read_text())
CFG = dict(rows=24_000, sessions=400, now=1_770_000_000.0, days=180,
           seconds_between_chunks=30, cluster_weights=[0.42, 0.13, 0.45],
           chunk_type_weights=[0.2, 0.45, 0.25, 0.1])


@pytest.fixture(scope="module")
def corpus():
    return C.generate(CFG, 2**31 + 11, HashEmbedding(128))


@pytest.mark.parametrize("text", sorted(GOLDEN["texts"]))
def test_embedding_matches_golden(text):
    got = HashEmbedding(128)(text)
    np.testing.assert_allclose(got, GOLDEN["texts"][text], rtol=0, atol=2e-7)


@pytest.mark.parametrize("token", sorted(GOLDEN["token_prefix16"]))
def test_token_vector_matches_golden(token):
    np.testing.assert_allclose(token_vector(token)[:16], GOLDEN["token_prefix16"][token],
                               rtol=0, atol=1e-7)


def test_bulk_embedding_equals_per_text(corpus):
    emb = HashEmbedding(128)
    for i in range(0, corpus.n, 2_999):
        np.testing.assert_allclose(corpus.matrix[i], emb(corpus.content(i)), atol=3e-7)
    np.testing.assert_allclose(np.linalg.norm(corpus.matrix, axis=1), 1.0, atol=1e-5)


def test_chunk_type_and_cluster_mix(corpus):
    types = np.bincount(corpus.ctype, minlength=4) / corpus.n
    np.testing.assert_allclose(types, CFG["chunk_type_weights"], atol=0.012)
    cluster = np.asarray(C.TOPIC_CLUSTER)[corpus.topic]
    np.testing.assert_allclose(np.bincount(cluster, minlength=3) / corpus.n,
                               CFG["cluster_weights"], atol=0.012)
    projects = np.bincount(corpus.project, minlength=4) / corpus.n
    assert projects.min() > 0.15 and projects.max() < 0.35


def test_sessions_and_timestamps(corpus):
    sizes = np.bincount(corpus.session)
    assert sizes.size == CFG["sessions"] and set(sizes) == {60}
    assert (np.diff(corpus.position)[np.diff(corpus.session) == 0] == 1).all()
    span = CFG["days"] * 86400.0
    assert corpus.timestamps.min() >= CFG["now"] - span
    assert corpus.timestamps.max() <= CFG["now"] + 60 * 30
    same = np.diff(corpus.session) == 0
    np.testing.assert_allclose(np.diff(corpus.timestamps)[same], 30.0)
    # the session start times spread over the whole 180 days
    assert np.ptp(corpus.timestamps) > 0.9 * span


def test_word_counts_per_cluster(corpus):
    counts = corpus.counts()
    reps = np.where(corpus.ctype == C.CHUNK_TYPES.index("assistant"), C.ASSISTANT_REPEAT, 1)
    words = counts.sum(axis=1) // reps
    cluster = np.asarray(C.TOPIC_CLUSTER)[corpus.topic]
    lo = [6 + 2 + 4, 6 + 2 + 1, 6]
    hi = [13 + 4 + 8, 13 + 4 + 2, 13 + 1]
    for c in range(3):
        w = words[cluster == c]
        assert w.min() >= lo[c] and w.max() <= hi[c]


def test_sql_rows_shape(corpus):
    rows = corpus.sql_rows()[:200]
    for r in rows:
        assert len(r) == 10 and r[2] in C.CHUNK_TYPES and r[6] in C.PROJECTS
        assert (r[7] is not None) == (r[2] == "tool_call")
        assert (r[8] is not None) == (r[2] == "file")
    assert len(corpus.session_rows()) == CFG["sessions"]


def test_same_seed_same_corpus():
    small = dict(CFG, rows=600, sessions=10)
    a = C.generate(small, 5, HashEmbedding(128))
    b = C.generate(small, 5, HashEmbedding(128))
    c = C.generate(small, 6, HashEmbedding(128))
    assert np.array_equal(a.matrix, b.matrix) and not np.array_equal(a.matrix, c.matrix)
