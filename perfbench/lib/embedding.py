"""The benchmark's fixed text embedding: a hash projection of tokens.

The same function as the program's ``HashEmbedder``: each lower-cased
alphanumeric token maps to a Gaussian vector seeded by blake2b of
``salt NUL token`` and tapered Matryoshka-style over a 256-d parent space; a
text is the sum of its token vectors, truncated to ``dim`` and
L2-normalised.  It lives here so that corpus and queries share one
embedding that no change to the program can move; golden vectors in
``perfbench/golden/embedding.json`` pin it.

Corpus rows are embedded in bulk: a text over a fixed vocabulary is a row of
token counts, so a block of rows is one ``counts @ token_vectors`` product.
"""

from __future__ import annotations

import hashlib
import re
from collections import OrderedDict
from typing import List, Sequence

import numpy as np

_TOKEN_RE = re.compile(r"[a-z0-9]+")
FULL_DIM = 256
SALT = "flexvec"


def token_vector(token: str, salt: str = SALT, full_dim: int = FULL_DIM) -> np.ndarray:
    """The tapered (full_dim,) float32 vector of one token."""
    digest = hashlib.blake2b(f"{salt}\x00{token}".encode("utf-8"), digest_size=8).digest()
    rng = np.random.Generator(np.random.PCG64(int.from_bytes(digest, "little")))
    v = rng.standard_normal(full_dim).astype(np.float32)
    taper = (1.0 / np.sqrt(1.0 + np.arange(full_dim) / 64.0)).astype(np.float32)
    return v * taper


def normalize_rows(v: np.ndarray) -> np.ndarray:
    """Row-wise L2 normalisation in float32 (zero rows stay zero)."""
    v = np.array(v, np.float32)
    nrm = np.sqrt(np.einsum("...i,...i->...", v, v))[..., None]
    v /= np.where(nrm > 1e-12, nrm, 1.0)
    return v


class HashEmbedding:
    """text -> (dim,) float32 unit vector; ``embed_counts`` for corpus blocks."""

    _CACHE = 1 << 16

    def __init__(self, dim: int = 128, salt: str = SALT, full_dim: int = FULL_DIM):
        if dim > full_dim:
            raise ValueError(f"dim {dim} exceeds the parent space {full_dim}")
        self.dim = dim
        self.salt = salt
        self.full_dim = full_dim
        self._vecs: "OrderedDict[str, np.ndarray]" = OrderedDict()

    def tokens(self, text: str) -> List[str]:
        return _TOKEN_RE.findall(text.lower())

    def vector(self, token: str) -> np.ndarray:
        v = self._vecs.get(token)
        if v is None:
            v = self._vecs[token] = token_vector(token, self.salt, self.full_dim)
            if len(self._vecs) > self._CACHE:
                self._vecs.popitem(last=False)
        return v

    def __call__(self, text: str) -> np.ndarray:
        acc = np.zeros(self.full_dim, np.float32)
        for t in self.tokens(text):
            acc += self.vector(t)
        return normalize_rows(acc[: self.dim])

    def vocab_matrix(self, vocab: Sequence[str]) -> np.ndarray:
        """(V, dim) float32 truncated token vectors of ``vocab``."""
        return np.stack([self.vector(w)[: self.dim] for w in vocab])

    def embed_counts(self, counts: np.ndarray, vocab_matrix: np.ndarray) -> np.ndarray:
        """Rows of token counts (n, V) -> (n, dim) float32 unit rows (float32
        sums, as a text's token vectors are summed)."""
        return normalize_rows(np.asarray(counts, np.float32) @ vocab_matrix)
