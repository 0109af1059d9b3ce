"""Sharded PEM scoring + top-k: the two-stage distributed retrieval path.

The naive pjit lowering of ``top_k(scores)`` over a row-sharded corpus
all-gathers the full (N, B) score panel before selecting.  This module's
``make_pem_topk`` is the shard_map formulation: every shard scores its own
corpus rows, selects a LOCAL top-k, and only the (shards * k, B) candidate
union crosses the interconnect — ``shards*k*B / (N*B)`` of the naive
collective traffic (the §Perf "flexvec-4" two_stage iteration).

Exactness: brute-force scoring is preserved (Bruch, *Foundations of Vector
Retrieval*: flat top-k is exact); the union of per-shard top-k provably
contains the global top-k, so the merge returns exactly the unsharded
result (fp reassociation of the per-shard matmuls aside).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.modulations import DEFAULT_DECAY_HALF_LIFE
from repro.dist.sharding import ShardingRules


def pem_topk_reference(
    corpus: jax.Array,      # (N, d) row-major chunk embeddings
    days: jax.Array,        # (N,) age in days
    q_pre: jax.Array,       # (d, B) pre-decay direction panel
    q_sup: jax.Array,       # (d, B) suppress panel
    k: int,
    *,
    half_life: float = DEFAULT_DECAY_HALF_LIFE,
) -> Tuple[jax.Array, jax.Array]:
    """Unsharded oracle: full-panel fused scoring + global top-k.

    Returns ``(indices, values)`` each (B, k), descending by score — the
    contract every sharded/fused lowering must reproduce exactly.
    """
    decay = 1.0 / (1.0 + days / half_life)
    scores = decay[:, None] * (corpus @ q_pre) + corpus @ q_sup  # (N, B)
    v, i = jax.lax.top_k(scores.T, k)
    return i, v


def union_merge_topk(
    v: jax.Array,       # (B, k_local) per-shard local top-k values
    gi: jax.Array,      # (B, k_local) matching GLOBAL row indices
    axes,               # mesh axis name(s) the corpus rows shard over
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Union merge, inside shard_map: gather every shard's local top-k
    candidates (shard-major order so equal scores keep the reference's
    smallest-global-index tie rule), then one top-k over the
    (B, shards*k_local) union.  Returns ``(indices, values)``, each
    (B, min(k, shards*k_local)) — the union provably contains the global
    top-k, so the merge is exact.

    Shared by :func:`make_pem_topk` and the ``sharded`` ExecutionBackend's
    fused ``score_select`` stage (repro/core/backends.py).  Its ops run
    under the ``merge`` stage scope, so a device trace reads the merge
    (all-gathers and union top-k) apart from the shard-local selection.
    """
    with jax.named_scope("merge"):
        cand_v = jax.lax.all_gather(v, axes)          # (shards, B, k_l)
        cand_i = jax.lax.all_gather(gi, axes)
        b = v.shape[0]
        union = cand_v.shape[0] * cand_v.shape[-1]    # shards * k_local
        cand_v = jnp.swapaxes(cand_v, 0, 1).reshape(b, union)
        cand_i = jnp.swapaxes(cand_i, 0, 1).reshape(b, union)
        vk, pos = jax.lax.top_k(cand_v, min(k, union))
        ik = jnp.take_along_axis(cand_i, pos, axis=1)
    return ik, vk


def union_merge_topk_payload(
    v: jax.Array,       # (B, k_local) per-shard local top-k values
    gi: jax.Array,      # (B, k_local) matching GLOBAL row indices
    pe: jax.Array,      # (B, k_local, d) matching row PAYLOAD (embeddings)
    axes,               # mesh axis name(s) the corpus rows shard over
    k: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`union_merge_topk` carrying a per-candidate PAYLOAD — the
    pool-row embeddings each shard gathered from its OWN row slice.

    The shard-local gather is the point: a diverse (MMR) tail needs the
    merged pool's embeddings, and gathering them after the merge reads
    the full replicated row space — O(N) traffic that grows with corpus
    size.  Gathering ``pe = matrix[i]`` inside the shard (O(n_local))
    and all-gathering it alongside the candidates keeps the collective
    at ``shards * k_local * (2 + d)`` elements, independent of N.

    The payload rides the SAME top-k permutation as the indices, so
    ``pk[b, j] == matrix[ik[b, j]]`` element-for-element and any
    consumer (the fused MMR tail) sees bit-identical inputs to the
    replicated-gather formulation.  Returns ``(indices, values,
    payload)``, each (B, min(k, union), ...), under the ``merge`` scope.
    """
    with jax.named_scope("merge"):
        cand_v = jax.lax.all_gather(v, axes)          # (shards, B, k_l)
        cand_i = jax.lax.all_gather(gi, axes)
        cand_p = jax.lax.all_gather(pe, axes)         # (shards, B, k_l, d)
        b = v.shape[0]
        union = cand_v.shape[0] * cand_v.shape[-1]    # shards * k_local
        d = cand_p.shape[-1]
        cand_v = jnp.swapaxes(cand_v, 0, 1).reshape(b, union)
        cand_i = jnp.swapaxes(cand_i, 0, 1).reshape(b, union)
        cand_p = jnp.swapaxes(cand_p, 0, 1).reshape(b, union, d)
        vk, pos = jax.lax.top_k(cand_v, min(k, union))
        ik = jnp.take_along_axis(cand_i, pos, axis=1)
        pk = jnp.take_along_axis(cand_p, pos[..., None], axis=1)
    return ik, vk, pk


def make_pem_topk(mesh: Mesh, rules: ShardingRules, k: int, raw: bool = False,
                  *, half_life: float = DEFAULT_DECAY_HALF_LIFE):
    """Build the shard_map'd corpus-row-sharded score -> local top-k -> merge.

    The corpus rows shard over ``rules.rules["corpus"]`` (mesh axes); query
    panels replicate.  ``raw=True`` returns the bare shard-mapped function
    for embedding inside a larger jitted graph (flexvec's two_stage step);
    ``raw=False`` returns it jitted for direct calls.

    Requires N divisible by the corpus shard count (callers pad the row
    grid — see ``FlexvecArch.build``).
    """
    axes = rules.rules.get("corpus")
    if axes is None:
        axes = ()
    elif isinstance(axes, str):
        axes = (axes,)
    else:
        axes = tuple(axes)
    axis_sizes = [mesh.shape[a] for a in axes]

    def sharded_topk(corpus, days, q_pre, q_sup):
        n_local = corpus.shape[0]
        # linear shard index in row-block order (major-first, matching the
        # PartitionSpec layout of P(("a", "b"), None) on dim 0)
        shard = jnp.int32(0)
        for a, size in zip(axes, axis_sizes):
            shard = shard * size + jax.lax.axis_index(a)

        decay = 1.0 / (1.0 + days / half_life)
        scores = decay[:, None] * (corpus @ q_pre) + corpus @ q_sup  # (n_l, B)

        k_local = min(k, n_local)
        v, i = jax.lax.top_k(scores.T, k_local)          # (B, k_local)
        gi = i + shard * n_local                          # global row ids

        if not axes:
            return gi, v

        return union_merge_topk(v, gi, axes, k)

    corpus_axes = axes if axes else None
    fn = jax.shard_map(
        sharded_topk,
        mesh=mesh,
        in_specs=(P(corpus_axes, None), P(corpus_axes), P(None, None), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return fn if raw else jax.jit(fn)
