"""The MS MARCO passage corpus as BEIR packages it (``msmarco``: 8,841,823
passages), embedded at 768-d, made from a seed.

No passage text or embedder weights are held here: each passage is a
noisy mixture of one to three topic vectors, and a topic is a word of a
synthetic passage vocabulary, drawn with Zipf weights.  A topic's vector is
the word's hashed token vector (``HashEmbedding`` at ``full_dim=768``), the
same vector a query's word embeds to, so a query made of vocabulary words
has real neighbours (the passages that share its topics) and not a flat
cosine bulk.  Rows are L2-normalised float32; there are no timestamps, so
``decay`` is not offered, and no text, so neither BM25 nor filters are.

Rows are made in fixed blocks of ``BLOCK`` rows, each from its own
generator seeded by ``SeedSequence.spawn`` (numpy's documented pattern for
parallel streams), on a pool of threads, one for each core this process may run on: the
matrix is bit-identical for a seed whatever the number of threads.  Each
thread writes into the matrix and a buffer of its own, so a block
allocates nothing of its size.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import List, Sequence

import ml_dtypes
import numpy as np

from perfbench.lib.embedding import HashEmbedding, token_vector
from perfbench.lib.reference import Reference, Scored

#: the passage vocabulary: how many topic words, and the Zipf exponent of
#: their weights (word j is drawn with weight (j + 1) ** -ZIPF)
TOPICS = 32_768
ZIPF = 1.0
#: topics in a passage (uniform over the range) and each one's weight
PASSAGE_TOPICS = (1, 3)
TOPIC_WEIGHT = (0.5, 1.0)
#: a passage's noise: one random tapered Gaussian (a token vector's
#: distribution) at this weight
NOISE = 0.5
#: rows per generation block (fixes each block's random stream)
BLOCK = 1 << 14
#: rows per block of the reference's scan
REF_BLOCK = 1 << 15
#: the stream of the corpus among the seed's streams
STREAM = 7

_CONSONANTS = "bdfghklmnprstvwz"
_VOWELS = "aeiou"


def cores() -> int:
    """The cores this process may run on (a machine's count can be many
    times its share, and every thread holds a block's temporaries)."""
    return len(os.sched_getaffinity(0))


def word(j: int) -> str:
    """Vocabulary word ``j``: three consonant-vowel syllables."""
    out = []
    for _ in range(3):
        j, r = divmod(j, len(_CONSONANTS) * len(_VOWELS))
        out.append(_CONSONANTS[r // len(_VOWELS)] + _VOWELS[r % len(_VOWELS)])
    return "".join(out)


VOCAB: List[str] = [word(j) for j in range(TOPICS)]
_CDF = np.cumsum(1.0 / np.arange(1, TOPICS + 1, dtype=np.float64) ** ZIPF)
_CDF /= _CDF[-1]


def draw_topics(rng: np.random.Generator, size) -> np.ndarray:
    """Topic (word) indices with the vocabulary's Zipf weights."""
    return np.minimum(np.searchsorted(_CDF, rng.random(size), side="right"), TOPICS - 1)


@dataclasses.dataclass
class Corpus:
    """What a system loads: ids and unit rows; no timestamps."""

    matrix: np.ndarray          # (n, dim) float32, L2-normalised
    timestamps = None

    @property
    def n(self) -> int:
        return int(self.matrix.shape[0])

    @property
    def ids(self) -> np.ndarray:
        return np.arange(self.n, dtype=np.int64)


def embedding(cfg: dict) -> HashEmbedding:
    return HashEmbedding(int(cfg["dim"]), full_dim=int(cfg["dim"]))


def topic_matrix(embedding: HashEmbedding) -> np.ndarray:
    """(TOPICS, dim) float32: each vocabulary word's token vector."""
    return np.stack([token_vector(w, embedding.salt, embedding.full_dim)[: embedding.dim]
                     for w in VOCAB])


def _fill_block(out: np.ndarray, topics: np.ndarray, taper: np.ndarray,
                seed: np.random.SeedSequence, scratch: np.ndarray) -> None:
    """One block of rows, in place; ``scratch`` is the thread's own
    (BLOCK, dim) buffer for the topic terms."""
    rng = np.random.default_rng(seed)
    n = out.shape[0]
    term = scratch[:n]
    count = rng.integers(PASSAGE_TOPICS[0], PASSAGE_TOPICS[1] + 1, size=n)
    ids = draw_topics(rng, (n, PASSAGE_TOPICS[1]))
    w = rng.uniform(*TOPIC_WEIGHT, size=(n, PASSAGE_TOPICS[1])).astype(np.float32)
    w[np.arange(PASSAGE_TOPICS[1])[None, :] >= count[:, None]] = 0.0
    rng.standard_normal(out.shape, dtype=np.float32, out=out)
    out *= taper * np.float32(NOISE)
    for j in range(PASSAGE_TOPICS[1]):
        # ids are in range by construction; the default mode ("raise")
        # would first gather into a temporary the size of ``term``
        np.take(topics, ids[:, j], axis=0, out=term, mode="clip")
        term *= w[:, j:j + 1]
        out += term
    out /= np.sqrt(np.einsum("ij,ij->i", out, out))[:, None]


def thread_buffer(local: threading.local, shape, dtype) -> np.ndarray:
    """The calling thread's own buffer in ``local``, made on first use."""
    buf = getattr(local, "buf", None)
    if buf is None:
        buf = local.buf = np.empty(shape, dtype)
    return buf


def generate(cfg: dict, seed: int, embedding: HashEmbedding) -> Corpus:
    n, dim = int(cfg["rows"]), int(embedding.dim)
    topics = topic_matrix(embedding)
    taper = (1.0 / np.sqrt(1.0 + np.arange(embedding.full_dim) / 64.0)).astype(np.float32)[:dim]
    matrix = np.empty((n, dim), np.float32)
    starts = range(0, n, BLOCK)
    seeds = np.random.SeedSequence([int(seed), STREAM]).spawn(len(starts))
    local = threading.local()

    def fill(lo: int, s: np.random.SeedSequence) -> None:
        _fill_block(matrix[lo:lo + BLOCK], topics, taper, s,
                    thread_buffer(local, (BLOCK, dim), np.float32))

    with ThreadPoolExecutor(max_workers=cores()) as pool:
        list(pool.map(fill, starts, seeds))
    return Corpus(matrix)


class PassageReference(Reference):
    """``lib/reference.py``'s brute force over the passages: every row
    scored in blocks of ``REF_BLOCK`` rows in float64 (bfloat16 rows and
    queries with float32 sums for the control), then top-k or MMR, with
    its semantics and answers unchanged.  The blocks run on a thread for
    each core (27 GB of rows cast to float64 one block at a time on one
    thread would take minutes), each casting its blocks into a buffer of
    its own: no block's copy is allocated anew.  The corpus has no
    timestamps and no text, so a request with ``decay``, a filter or a
    lexical leg is refused, never answered."""

    def __init__(self, corpus: Corpus, embedding: HashEmbedding, precision: str = "f64"):
        if precision not in ("f64", "bf16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.corpus = corpus
        self.embedding = embedding
        self.precision = precision
        self.days = None
        self._bm25 = None

    def score(self, specs: Sequence[dict]) -> List[Scored]:
        dt = self._dtype()
        cols, slices = [], []
        for spec in specs:
            self.eligible(spec)
            d = self.directions(spec)
            slices.append((len(cols), len(cols) + len(d)))
            cols += d
        panel = self._vec(np.stack(cols, axis=1)).astype(dt)
        n, dim = self.corpus.matrix.shape
        dots = np.empty((n, panel.shape[1]), dt)
        local, low = threading.local(), threading.local()

        def block(lo: int) -> None:
            hi = min(lo + REF_BLOCK, n)
            rows = thread_buffer(local, (REF_BLOCK, dim), dt)[:hi - lo]
            if self.precision == "bf16":
                half = thread_buffer(low, (REF_BLOCK, dim), ml_dtypes.bfloat16)[:hi - lo]
                np.copyto(half, self.corpus.matrix[lo:hi], casting="same_kind")
                np.copyto(rows, half)
            else:
                np.copyto(rows, self.corpus.matrix[lo:hi])
            np.matmul(rows, panel, out=dots[lo:hi])

        with ThreadPoolExecutor(max_workers=cores()) as pool:
            list(pool.map(block, range(0, n, REF_BLOCK)))
        return [Scored(self._combine(spec, dots[:, a:b]), n)
                for spec, (a, b) in zip(specs, slices)]

    def eligible(self, spec: dict):
        if spec.get("filter"):
            raise ValueError("the passage corpus has no fields to filter on")
        return None

    def _combine(self, spec: dict, d: np.ndarray) -> np.ndarray:
        if spec.get("decay") is not None or spec.get("hybrid") is not None:
            raise ValueError("the passage corpus has no timestamps or text: "
                             "no decay or hybrid")
        return super()._combine(spec, d)


def reference(corpus: Corpus, embedding: HashEmbedding, precision: str) -> PassageReference:
    return PassageReference(corpus, embedding, precision=precision)
