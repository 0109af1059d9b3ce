"""The agent-history corpus (flexvec, sec. 4): sessions of chunks over 16
topics, with timestamps, projects, chunk types and text.

A corpus module gives the harness three things, which it finds by the
configuration's ``corpus`` key (``perfbench/corpora/<corpus>.py``):

- ``embedding(cfg)``: the text embedding that the corpus rows and the
  queries share;
- ``generate(cfg, seed, embedding)``: the corpus from the seed, with at
  least ``n``, ``ids`` and ``matrix`` (what a system loads);
- ``reference(corpus, embedding, precision)``: the plain reference that
  ``lib/check.py`` and ``control.py`` score with (``score``, ``answer``,
  ``rows``); ``precision`` is ``"f64"``, or ``"bf16"`` for the control.

This one is ``lib/corpus.py``, ``lib/embedding.py`` and
``lib/reference.py`` as they are.
"""

from __future__ import annotations

from perfbench.lib import corpus as _corpus
from perfbench.lib.embedding import HashEmbedding
from perfbench.lib.reference import Reference


def embedding(cfg: dict) -> HashEmbedding:
    return HashEmbedding(int(cfg["dim"]))


def generate(cfg: dict, seed: int, embedding: HashEmbedding) -> _corpus.Corpus:
    return _corpus.generate(cfg, seed, embedding)


def reference(corpus: _corpus.Corpus, embedding: HashEmbedding, precision: str) -> Reference:
    return Reference(corpus, embedding, precision=precision)
