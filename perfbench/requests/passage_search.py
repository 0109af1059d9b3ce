"""``search(tokens, k)`` over the passage corpus, with passage-like queries.

A query is ``lo`` to ``hi - 1`` words of the passage vocabulary
(``perfbench/corpora/msmarco_passage.py``) with the corpus's own Zipf
skew; a suppress text is words of other topics than the query's.  The
answer is the engine's ``(id, score)`` list, sent and warmed as
``perfbench/requests/search.py`` does: every pow2 batch bucket the clients
can fill, with every combination of the mix's plan kinds.
"""

from __future__ import annotations

from typing import Iterable

import numpy as np

from perfbench.corpora.msmarco_passage import VOCAB, draw_topics
from perfbench.lib.traffic import base_spec
from perfbench.requests.search import call, submit, warm  # noqa: F401

SURFACE = "search"


def words(rng: np.random.Generator, spec: dict, avoid: Iterable[str] = ()) -> str:
    """``spec["words"] = [lo, hi]``: lo to hi - 1 distinct vocabulary words,
    none of them in ``avoid``."""
    lo, hi = spec["words"]
    want, taken = int(rng.integers(lo, hi)), set(avoid)
    out = []
    while len(out) < want:
        w = VOCAB[int(draw_topics(rng, None))]
        if w not in taken:
            taken.add(w)
            out.append(w)
    return " ".join(out)


def make(rng: np.random.Generator, entry: dict, traffic: dict) -> dict:
    spec = base_spec(entry, SURFACE, traffic["k"])
    spec["similar"] = words(rng, entry["similar"])
    spec["suppress"] = [words(rng, x, spec["similar"].split())
                        for x in entry.get("suppress", ())]
    parts = [f"similar:{spec['similar']}"] + [f"suppress:{x}" for x in spec["suppress"]]
    if spec["diverse"]:
        parts.append("diverse")
    spec["tokens"] = spec["text"] = " ".join(parts)
    return spec
