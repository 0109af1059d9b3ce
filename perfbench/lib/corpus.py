"""The agent-history corpus, generated in bulk from a seed.

A vectorised copy of the distribution the program's ``data/corpus.py``
draws one chunk at a time: the same topic vocabularies, cluster weights,
chunk-type mix, projects, session layout, timestamp spread and word-count
ranges.  Rows are kept as token counts over the fixed vocabulary, so the
embedding of a block is one matrix product and the reference can score
BM25 without a text index.  Words keep the order topic, overlap, shared
(the original shuffles them; no score here depends on word order).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from perfbench.lib.embedding import HashEmbedding

# -- distribution tables (copied from the program's data/corpus.py) ----------

OVERLAP = ["system", "works", "architecture", "how", "the", "overview"]
DESCRIPTIVE_SHARED = [
    "website", "landing", "page", "design", "tagline",
    "documentation", "readme", "community", "post", "draft", "copy",
]
IMPLEMENTATION_SHARED = ["implementation", "internal", "logic", "code"]
DESCRIPTIVE_TOPICS = [
    ("ui_style", ["website", "landing", "page", "design", "style", "layout", "css", "iteration"]),
    ("tagline", ["marketing", "tagline", "draft", "copy", "headline", "brand", "positioning"]),
    ("docs_site", ["documentation", "readme", "site", "structure", "guide", "tutorial"]),
    ("positioning", ["product", "positioning", "discussion", "market", "pitch", "story"]),
    ("community", ["community", "post", "announcement", "launch", "blog", "share"]),
]
IMPLEMENTATION_TOPICS = [
    ("identity", ["identity", "layer", "data", "model", "uuid", "provenance", "tracking"]),
    ("server", ["server", "lifecycle", "debugging", "restart", "socket", "operations"]),
    ("worker", ["background", "worker", "failure", "analysis", "queue", "retry"]),
    ("rendering", ["rendering", "pipeline", "implementation", "frame", "buffer", "draw"]),
    ("platform", ["platform", "detection", "branching", "logic", "linux", "darwin"]),
]
NEUTRAL_TOPICS = [
    ("auth", ["auth", "token", "jwt", "login", "session", "oauth", "refresh"]),
    ("database", ["database", "sqlite", "storage", "schema", "migration", "index"]),
    ("search", ["search", "retrieval", "embedding", "vector", "score", "ranking"]),
    ("testing", ["test", "pytest", "assert", "fixture", "coverage", "mock"]),
    ("deploy", ["deploy", "release", "docker", "build", "publish", "version"]),
    ("files", ["file", "path", "snapshot", "diff", "edit", "patch"]),
]
PROJECTS = ["core", "website", "cli", "infra"]
TOOLS = ["read", "edit", "bash", "grep", "write"]
CHUNK_TYPES = ["user_prompt", "assistant", "tool_call", "file"]
CLUSTERS = ["descriptive", "implementation", "neutral"]
TOPICS = DESCRIPTIVE_TOPICS + IMPLEMENTATION_TOPICS + NEUTRAL_TOPICS
TOPIC_CLUSTER = [0] * len(DESCRIPTIVE_TOPICS) + [1] * len(IMPLEMENTATION_TOPICS) \
    + [2] * len(NEUTRAL_TOPICS)

# word-count ranges per chunk, [lo, hi): topic words, overlap words by
# cluster, shared words by cluster (data/corpus.py ``_make_content``)
TOPIC_WORDS = (6, 14)
OVERLAP_WORDS = ((2, 5), (2, 5), (0, 2))
SHARED_WORDS = ((4, 9), (1, 3), (0, 1))
SHARED_LISTS = (DESCRIPTIVE_SHARED, IMPLEMENTATION_SHARED, [])
ASSISTANT_REPEAT = 4  # assistant bodies are the word list four times over


def _vocabulary() -> List[str]:
    out: Dict[str, None] = {}
    for w in OVERLAP + DESCRIPTIVE_SHARED + IMPLEMENTATION_SHARED:
        out.setdefault(w)
    for _, words in TOPICS:
        for w in words:
            out.setdefault(w)
    return list(out)


VOCAB = _vocabulary()
WORD_ID = {w: i for i, w in enumerate(VOCAB)}


@dataclasses.dataclass
class Corpus:
    """One generated deployment's rows (row i has chunk id i)."""

    matrix: np.ndarray        # (n, dim) float32, unit rows
    timestamps: np.ndarray    # (n,) float64 unix seconds
    ctype: np.ndarray         # (n,) int8 index into CHUNK_TYPES
    project: np.ndarray       # (n,) int8 index into PROJECTS
    session: np.ndarray       # (n,) int32
    position: np.ndarray      # (n,) int32
    topic: np.ndarray         # (n,) int8 index into TOPICS
    words: np.ndarray         # (n, W) int16 vocabulary ids, -1 = none
    tool: np.ndarray          # (n,) int8 index into TOOLS (tool_call rows)
    file_no: np.ndarray       # (n,) int8 file number (file rows)
    now: float

    @property
    def n(self) -> int:
        return int(self.matrix.shape[0])

    @property
    def ids(self) -> np.ndarray:
        return np.arange(self.n, dtype=np.int64)

    def counts(self, rows: Optional[np.ndarray] = None) -> np.ndarray:
        """(rows, V) token counts of the chunks' content."""
        words = self.words if rows is None else self.words[rows]
        ctype = self.ctype if rows is None else self.ctype[rows]
        return _counts(words, ctype)

    def content(self, i: int) -> str:
        body = " ".join(VOCAB[w] for w in self.words[i] if w >= 0)
        if self.ctype[i] == CHUNK_TYPES.index("assistant"):
            body = " ".join([body] * ASSISTANT_REPEAT)
        return body

    def sql_rows(self) -> List[tuple]:
        """``(id, session_id, type, content, created_at, position, project,
        tool_name, file, ext)`` — the shape of the chunks table."""
        out = []
        tool_call, file_t = CHUNK_TYPES.index("tool_call"), CHUNK_TYPES.index("file")
        for i in range(self.n):
            t = int(self.ctype[i])
            tool = TOOLS[self.tool[i]] if t == tool_call else None
            path = ext = None
            if t == file_t:
                name = TOPICS[self.topic[i]][0]
                path, ext = f"src/{name}/{name}_{int(self.file_no[i])}.py", "py"
            out.append((i, f"s{int(self.session[i]):06d}", CHUNK_TYPES[t],
                        self.content(i), float(self.timestamps[i]),
                        int(self.position[i]), PROJECTS[self.project[i]],
                        tool, path, ext))
        return out

    def session_rows(self) -> List[tuple]:
        """``(session_id, project, title, start_time, end_time, count)``."""
        n_s = int(self.session.max()) + 1
        start = np.full(n_s, np.inf)
        end = np.full(n_s, -np.inf)
        np.minimum.at(start, self.session, self.timestamps)
        np.maximum.at(end, self.session, self.timestamps)
        count = np.bincount(self.session, minlength=n_s)
        first = np.searchsorted(self.session, np.arange(n_s))
        return [(f"s{s:06d}", PROJECTS[self.project[first[s]]], f"session s{s:06d}",
                 float(start[s]), float(end[s]), int(count[s])) for s in range(n_s)]


def _counts(words: np.ndarray, ctype: np.ndarray) -> np.ndarray:
    n = words.shape[0]
    counts = np.zeros((n, len(VOCAB)), np.uint8)
    rows = np.arange(n)
    for j in range(words.shape[1]):  # one word per row per slot: no repeats
        w = words[:, j]
        keep = w >= 0
        counts[rows[keep], w[keep]] += 1
    counts[ctype == CHUNK_TYPES.index("assistant")] *= ASSISTANT_REPEAT
    return counts


def _draw_words(rng: np.random.Generator, cluster: np.ndarray,
                topic: np.ndarray) -> np.ndarray:
    n = cluster.shape[0]
    tlen = np.asarray([len(w) for _, w in TOPICS])
    table = np.full((len(TOPICS), tlen.max()), -1, np.int16)
    for t, (_, words) in enumerate(TOPICS):
        table[t, : len(words)] = [WORD_ID[w] for w in words]
    parts = []
    lo, hi = TOPIC_WORDS
    k = rng.integers(lo, hi, size=n)
    pick = (rng.random((n, hi - 1)) * tlen[topic][:, None]).astype(np.int64)
    w = table[topic[:, None], pick]
    parts.append(np.where(np.arange(hi - 1)[None, :] < k[:, None], w, -1))

    over_ids = np.asarray([WORD_ID[w] for w in OVERLAP], np.int16)
    width = max(h for _, h in OVERLAP_WORDS) - 1
    k = np.zeros(n, np.int64)
    for c, (lo, hi) in enumerate(OVERLAP_WORDS):
        sel = cluster == c
        k[sel] = rng.integers(lo, hi, size=int(sel.sum()))
    w = over_ids[rng.integers(0, len(OVERLAP), size=(n, width))]
    parts.append(np.where(np.arange(width)[None, :] < k[:, None], w, -1))

    width = max(h for _, h in SHARED_WORDS) - 1
    k = np.zeros(n, np.int64)
    w = np.full((n, width), -1, np.int16)
    for c, ((lo, hi), lst) in enumerate(zip(SHARED_WORDS, SHARED_LISTS)):
        sel = np.flatnonzero(cluster == c)
        if not lst:
            continue
        k[sel] = rng.integers(lo, hi, size=sel.size)
        ids = np.asarray([WORD_ID[x] for x in lst], np.int16)
        w[sel] = ids[rng.integers(0, len(lst), size=(sel.size, width))]
    parts.append(np.where(np.arange(width)[None, :] < k[:, None], w, -1))
    return np.concatenate(parts, axis=1).astype(np.int16)


def generate(cfg: dict, seed: int, embedding: HashEmbedding,
             block: int = 1 << 17) -> Corpus:
    """The configuration's corpus from ``seed`` (stream 0 of the seed)."""
    rng = np.random.default_rng([seed, 0])
    n, n_s = int(cfg["rows"]), int(cfg["sessions"])
    per = n // n_s
    sizes = np.full(n_s, per, np.int64)
    sizes[: n - per * n_s] += 1
    session = np.repeat(np.arange(n_s, dtype=np.int32), sizes)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    position = (np.arange(n) - starts[session]).astype(np.int32)
    s_project = rng.integers(0, len(PROJECTS), size=n_s).astype(np.int8)
    s_t0 = cfg["now"] - rng.uniform(0.0, cfg["days"] * 86400.0, size=n_s)
    timestamps = s_t0[session] + position * float(cfg["seconds_between_chunks"])

    cluster = rng.choice(len(CLUSTERS), p=cfg["cluster_weights"], size=n)
    per_cluster = [np.flatnonzero(np.asarray(TOPIC_CLUSTER) == c) for c in range(3)]
    topic = np.zeros(n, np.int8)
    for c, members in enumerate(per_cluster):
        sel = cluster == c
        topic[sel] = members[rng.integers(0, members.size, size=int(sel.sum()))]
    ctype = rng.choice(len(CHUNK_TYPES), p=cfg["chunk_type_weights"], size=n).astype(np.int8)
    words = _draw_words(rng, cluster, topic.astype(np.int64))
    tool = rng.integers(0, len(TOOLS), size=n).astype(np.int8)
    file_no = rng.integers(0, 20, size=n).astype(np.int8)

    vm = embedding.vocab_matrix(VOCAB)
    matrix = np.empty((n, embedding.dim), np.float32)
    for i in range(0, n, block):
        matrix[i:i + block] = embedding.embed_counts(
            _counts(words[i:i + block], ctype[i:i + block]), vm)
    return Corpus(matrix=matrix, timestamps=timestamps, ctype=ctype,
                  project=s_project[session], session=session, position=position,
                  topic=topic, words=words, tool=tool, file_no=file_no,
                  now=float(cfg["now"]))


def topic_words(topic: int) -> Sequence[str]:
    return TOPICS[topic][1]
