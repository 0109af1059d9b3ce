"""The comparison that decides ``correct``.

Every answer the window produced for a sampled request is held to the
reference's scores for that request, through four numbers:

- ``rank_gap``: teacher-forced regret.  Walking the answer in its own
  order, each pick's reference objective is compared with the best the
  reference could have picked given the answer's earlier picks: relevance
  for a ranking, the MMR objective (``lam * rel - (1 - lam) * max sim``) for
  a diverse answer returned in pick order.  Near ties that two summation
  orders break differently read as tiny gaps; a wrong row reads large, and
  an ineligible or repeated row reads infinite.
- ``score_gap``: the largest |answer score - reference score| of the rows
  returned (raw relevance for ``search``, the min-max normalised value for
  SQL, normalised over the reference's own answer).
- ``set_miss``: for a diverse SQL answer, which SQL returns sorted by score
  so the pick order is gone: the share of its rows that the reference's
  MMR picks do not hold.
- ``unanswered``: requests that failed, or returned another number of rows
  than the reference.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from perfbench.lib.reference import (MMR_LAMBDA, MMR_OVERSAMPLE, Answer, Reference,
                                     Scored, pool_of, top_rows)

NUMBERS = ("rank_gap", "score_gap", "set_miss", "unanswered")


def ranking_regret(s: np.ndarray, got_ids: Sequence[int]) -> float:
    """Largest teacher-forced regret of a ranking under scores ``s``."""
    head = top_rows(s, min(s.size, 2 * len(got_ids) + 8))
    picked, p, worst = set(), 0, 0.0
    for g in got_ids:
        if g in picked or not 0 <= g < s.size or np.isneginf(s[g]):
            return float("inf")
        while p < head.size and int(head[p]) in picked:
            p += 1
        best = s[head[p]] if p < head.size else s[g]
        worst = max(worst, float(best - s[g]))
        picked.add(g)
    return worst


def mmr_regret(ref: Reference, s: np.ndarray, pool: np.ndarray,
               got_ids: Sequence[int], lam: float) -> float:
    """Largest teacher-forced MMR regret of a diverse answer in pick order."""
    emb = ref.rows(pool).astype(np.float64)
    rel = s[pool].astype(np.float64)
    max_sim = np.full(pool.size, -np.inf)
    taken = np.zeros(pool.size, bool)
    where = {int(r): j for j, r in enumerate(pool)}
    picked_vecs: List[np.ndarray] = []
    worst = 0.0
    for g in got_ids:
        if not 0 <= g < s.size or np.isneginf(s[g]):
            return float("inf")
        obj = lam * rel - (1 - lam) * np.where(np.isneginf(max_sim), 0.0, max_sim)
        best = float(np.where(taken, -np.inf, obj).max())
        j = where.get(int(g))
        if j is not None:
            if taken[j]:
                return float("inf")
            og = float(obj[j])
        else:
            v = ref.rows([g])[0].astype(np.float64)
            pen = max((float(v @ u) for u in picked_vecs), default=None)
            og = lam * float(s[g]) - (1 - lam) * (0.0 if pen is None else pen)
        worst = max(worst, best - og)
        v = ref.rows([g])[0].astype(np.float64)
        picked_vecs.append(v)
        max_sim = np.maximum(max_sim, emb @ v)
        if j is not None:
            taken[j] = True
    return worst


def compare(ref: Reference, spec: dict, scored: Scored,
            got: Optional[Answer]) -> Dict[str, float]:
    """The four numbers for one request's answer (``got`` None = failed)."""
    out = {"rank_gap": 0.0, "score_gap": 0.0, "set_miss": 0.0, "unanswered": 0.0}
    s = scored.scores
    want = ref.answer(spec, scored)
    if got is None or len(got) != len(want):
        out["unanswered"] = 1.0
        return out
    ids = [int(i) for i, _ in got]
    vals = np.asarray([v for _, v in got], np.float64)
    if spec["surface"] == "sql":
        want_ids = [i for i, _ in want]
        raw = s[want_ids]
        lo, hi = raw.min(), raw.max()
        rel = s[np.clip(ids, 0, s.size - 1)]
        expect = np.ones(len(ids)) if hi == lo else (rel - lo) / (hi - lo)
    else:
        expect = s[np.clip(ids, 0, s.size - 1)]
    bad = np.asarray([not 0 <= i < s.size for i in ids]) | np.isneginf(expect)
    out["score_gap"] = float("inf") if bad.any() else float(np.abs(vals - expect).max(initial=0.0))
    if spec.get("diverse") and spec["surface"] == "sql":
        miss = set(ids) - {i for i, _ in want}
        out["set_miss"] = len(miss) / max(1, len(want))
    elif spec.get("diverse"):
        k = spec["k"] if spec.get("k") is not None else pool_of(spec)
        width = min(MMR_OVERSAMPLE * max(k, pool_of(spec)), scored.n_eligible)
        out["rank_gap"] = mmr_regret(ref, s, top_rows(s, width), ids, MMR_LAMBDA)
    else:
        out["rank_gap"] = ranking_regret(s, ids)
    return out


def compare_all(ref: Reference, specs: Sequence[dict],
                got: Sequence[Optional[Answer]]) -> Dict[str, float]:
    """Worst of each number over the requests (``unanswered`` is a count)."""
    worst = {k: 0.0 for k in NUMBERS}
    for spec, sc, g in zip(specs, ref.score(specs), got):
        for k, v in compare(ref, spec, sc, g).items():
            worst[k] = worst[k] + v if k == "unanswered" else max(worst[k], v)
    return worst


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is within its limit (inclusive)."""
    return all(numbers[k] <= limits[k] for k in NUMBERS)
