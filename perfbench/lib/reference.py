"""The plain reference: the paper's modulation semantics in numpy.

Written from the paper's Table 1 and the query grammar, not from the
program: float64 brute force over every row, with no plan folding, no
batching, no device and no index.  ``precision="bf16"`` computes the same
thing with the matrix and query vectors rounded to bfloat16 and float32
accumulation — the control, which the comparison has to reject.

Semantics (fixed order: base similarity, trajectory, decay, suppress):

    s  = (1 - b) * M q + b * M (to - from)          trajectory, b = 0.5
    s *= 1 / (1 + days / N)                         decay:N
    s -= 0.5 * M x                                  each suppress:X
    s  = W * s + (1 - W) * minmax(bm25) on hits     hybrid_search(text, W)
    diverse: MMR (lambda 0.7) over the top 3 * max(k, pool) by s

Rows outside a Phase-1 filter never score.  A SQL answer min-max
normalises its scores over the rows it returns.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

from perfbench.lib.corpus import CHUNK_TYPES, PROJECTS, WORD_ID, Corpus
from perfbench.lib.embedding import HashEmbedding

SUPPRESS_WEIGHT = 0.5
TRAJECTORY_BLEND = 0.5
MMR_LAMBDA = 0.7
MMR_OVERSAMPLE = 3
DEFAULT_POOL = 500
SECONDS_PER_DAY = 86400.0
BM25_K1, BM25_B = 1.2, 0.75

Answer = List[Tuple[int, float]]


def unit(v: np.ndarray) -> np.ndarray:
    v = np.asarray(v, np.float64)
    n = np.sqrt((v * v).sum())
    return v / n if n > 0 else v


@dataclasses.dataclass
class Scored:
    """One request's reference scores over every row (-inf = ineligible)."""

    scores: np.ndarray   # (n,) float64 (float32 values for the control)
    n_eligible: int


class Reference:
    """Scores, answers and BM25 over one generated corpus."""

    def __init__(self, corpus: Corpus, embedding: HashEmbedding,
                 precision: str = "f64", block: int = 1 << 17):
        if precision not in ("f64", "bf16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.corpus = corpus
        self.embedding = embedding
        self.precision = precision
        self.block = block
        self.days = np.maximum((corpus.now - corpus.timestamps) / SECONDS_PER_DAY, 0.0)
        self._bm25: Optional[Tuple[np.ndarray, np.ndarray, float]] = None

    # -- arithmetic in the chosen precision ---------------------------------

    def _vec(self, v: np.ndarray) -> np.ndarray:
        if self.precision == "bf16":
            return np.asarray(v, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)
        return np.asarray(v, np.float64)

    def _rows(self, lo: int, hi: int) -> np.ndarray:
        return self._vec(self.corpus.matrix[lo:hi])

    def rows(self, ids: Sequence[int]) -> np.ndarray:
        return self._vec(self.corpus.matrix[np.asarray(ids, np.int64)])

    def _dtype(self):
        return np.float32 if self.precision == "bf16" else np.float64

    # -- one request ---------------------------------------------------------

    def directions(self, spec: dict) -> List[np.ndarray]:
        """[query, trajectory?, suppress...] as unit (or difference) vectors."""
        e = self.embedding
        out = [unit(e(spec["similar"]))]
        if spec.get("from") is not None:
            out.append(unit(e(spec["to"])) - unit(e(spec["from"])))
        out += [unit(e(x)) for x in spec.get("suppress", ())]
        return out

    def eligible(self, spec: dict) -> Optional[np.ndarray]:
        f = spec.get("filter")
        if not f:
            return None
        m = np.ones(self.corpus.n, bool)
        if "type" in f:
            m &= self.corpus.ctype == CHUNK_TYPES.index(f["type"])
        if "project" in f:
            m &= self.corpus.project == PROJECTS.index(f["project"])
        return m

    def score(self, specs: Sequence[dict]) -> List[Scored]:
        """Reference scores of every request, in one blocked pass."""
        dt = self._dtype()
        cols, slices = [], []
        for spec in specs:
            d = self.directions(spec)
            slices.append((len(cols), len(cols) + len(d)))
            cols += d
        panel = self._vec(np.stack(cols, axis=1)).astype(dt)
        n = self.corpus.n
        dots = np.empty((n, panel.shape[1]), dt)
        for lo in range(0, n, self.block):
            hi = min(lo + self.block, n)
            dots[lo:hi] = self._rows(lo, hi).astype(dt) @ panel
        out = []
        for spec, (a, b) in zip(specs, slices):
            s = self._combine(spec, dots[:, a:b])
            m = self.eligible(spec)
            if m is not None:
                s = np.where(m, s, -np.inf)
            out.append(Scored(s, n if m is None else int(m.sum())))
        return out

    def _combine(self, spec: dict, d: np.ndarray) -> np.ndarray:
        dt = self._dtype()
        s = d[:, 0].copy()
        j = 1
        if spec.get("from") is not None:
            s = (1.0 - TRAJECTORY_BLEND) * s + TRAJECTORY_BLEND * d[:, 1]
            j = 2
        if spec.get("decay") is not None:
            s = s * (1.0 / (1.0 + self.days.astype(dt) / dt(spec["decay"])))
        for c in range(j, d.shape[1]):
            s = s - SUPPRESS_WEIGHT * d[:, c]
        if spec.get("hybrid") is not None:
            w = dt(spec["hybrid"])
            s = w * s + (1 - w) * self.lexical(spec["keyword"], pool_of(spec))
        return s.astype(dt)

    # -- the lexical leg: FTS5's BM25 over the chunk text --------------------

    def bm25(self, terms: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
        """(rows matching every term, their BM25) — SQLite FTS5's formula:
        idf = ln((N - n + 0.5) / (n + 0.5)), floored at 1e-6, k1 1.2, b 0.75,
        summed over query terms (an implicit AND)."""
        if self._bm25 is None:
            counts = self.corpus.counts()
            dl = counts.sum(axis=1).astype(np.float64)
            self._bm25 = (counts, dl, float(dl.mean()))
        counts, dl, avgdl = self._bm25
        n = counts.shape[0]
        ids = [WORD_ID.get(t) for t in terms]
        if any(i is None for i in ids):
            return np.empty(0, np.int64), np.empty(0)
        tf = counts[:, ids].astype(np.float64)
        rows = np.flatnonzero((tf > 0).all(axis=1))
        total = np.zeros(rows.size)
        for c in range(len(ids)):
            hit = int(np.count_nonzero(counts[:, ids[c]]))
            idf = np.log((n - hit + 0.5) / (hit + 0.5))
            idf = idf if idf > 0 else 1e-6
            f = tf[rows, c]
            total += idf * (f * (BM25_K1 + 1)) / (
                f + BM25_K1 * (1 - BM25_B + BM25_B * dl[rows] / avgdl))
        return rows, total

    def lexical(self, text: str, pool: int) -> np.ndarray:
        """(n,) min-max normalised BM25 of the top-``pool`` hits, 0 elsewhere.
        Rows tied at the cut normalise to 0, in or out of the pool alike."""
        out = np.zeros(self.corpus.n, self._dtype())
        rows, sc = self.bm25(self.embedding.tokens(text))
        if rows.size == 0:
            return out
        order = np.argsort(-sc, kind="stable")[:pool]
        rows, sc = rows[order], sc[order]
        lo, hi = sc.min(), sc.max()
        out[rows] = 1.0 if hi == lo else (sc - lo) / (hi - lo)
        return out

    # -- answers -------------------------------------------------------------

    def answer(self, spec: dict, scored: Scored) -> Answer:
        """The answer the surface should return for ``spec``."""
        s = scored.scores
        k = spec["k"] if spec.get("k") is not None else pool_of(spec)
        length = min(k, scored.n_eligible)
        if spec.get("diverse"):
            pool = top_rows(s, min(MMR_OVERSAMPLE * max(k, pool_of(spec)), scored.n_eligible))
            picks = pool[mmr(self.rows(pool), s[pool], length, MMR_LAMBDA)]
        else:
            picks = top_rows(s, length)
        ans = [(int(i), float(s[i])) for i in picks]
        return sql_order(ans) if spec["surface"] == "sql" else ans


def pool_of(spec: dict) -> int:
    return int(spec.get("pool") or DEFAULT_POOL)


def top_rows(s: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k largest scores, descending, ties to the lower row."""
    if k <= 0:
        return np.empty(0, np.int64)
    if k >= s.size:
        return np.argsort(-s, kind="stable")
    part = np.argpartition(-s, k - 1)[:k]
    cut = s[part].min()
    members = np.concatenate([np.flatnonzero(s > cut), np.flatnonzero(s == cut)])[:k]
    return members[np.argsort(-s[members], kind="stable")]


def mmr(emb: np.ndarray, rel: np.ndarray, k: int, lam: float) -> np.ndarray:
    """Greedy MMR positions: argmax of lam*rel - (1-lam)*max sim to earlier
    picks (no penalty before the first), first index on ties."""
    n = rel.shape[0]
    k = min(k, n)
    sims = emb @ emb.T
    max_sim = np.full(n, -np.inf)
    taken = np.zeros(n, bool)
    out = np.empty(k, np.int64)
    for i in range(k):
        obj = lam * rel - (1 - lam) * np.where(np.isneginf(max_sim), 0.0, max_sim)
        obj = np.where(taken, -np.inf, obj)
        j = int(np.argmax(obj))
        out[i] = j
        taken[j] = True
        max_sim = np.maximum(max_sim, sims[j])
    return out


def normalize_scores(values: Sequence[float]) -> List[float]:
    v = np.asarray(values, np.float64)
    if v.size == 0:
        return []
    lo, hi = v.min(), v.max()
    return [1.0] * v.size if hi == lo else list((v - lo) / (hi - lo))


def sql_order(ans: Answer) -> Answer:
    """A SQL answer: scores min-max normalised over the answer, rows in
    ``ORDER BY score DESC, id`` order."""
    norm = normalize_scores([s for _, s in ans])
    return sorted(((i, float(v)) for (i, _), v in zip(ans, norm)),
                  key=lambda r: (-r[1], r[0]))


def answers(ref: Reference, specs: Sequence[dict]) -> Dict[int, Answer]:
    return {j: ref.answer(spec, sc) for j, (spec, sc) in enumerate(zip(specs, ref.score(specs)))}
