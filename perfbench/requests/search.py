"""``search(tokens, k)`` through the deployment's batched engine.

A request is one ``vec_ops`` token string (``perfbench/lib/traffic.py``)
and the traffic's ``k``; its answer is the engine's ``(id, score)`` list.
Needs a deployment with ``engine`` (a ``BatchedRetrievalEngine``),
``cache`` (its ``VectorCache``), ``backend`` and ``now``.
"""

from __future__ import annotations

import itertools
from typing import Dict, Iterable, List

from repro.core.backends import score_select_segments
from repro.core.grammar import parse

from perfbench.lib.traffic import modulated

SURFACE = "search"


def make(rng, entry: dict, traffic: dict) -> dict:
    spec = modulated(rng, entry, SURFACE, traffic.get("k"))
    spec["text"] = spec["tokens"]
    return spec


def call(system, spec: dict) -> list:
    return system.engine.search(spec["text"], spec["k"], timeout=60.0)


async def submit(system, spec: dict) -> list:
    return await system.engine.asearch(spec["text"], spec["k"])


def pow2_buckets(limit: int) -> List[int]:
    out, b = [], 1
    while b <= limit:
        out.append(b)
        b *= 2
    if out[-1] < limit:
        out.append(b)
    return out


def _combos(names: List[str], size: int) -> Iterable[tuple]:
    for r in range(1, min(size, len(names)) + 1):
        yield from itertools.combinations(names, r)


def warm(system, traffic: dict, samples: Dict[str, dict]) -> int:
    """Compile (or load from the persistent cache) every batch shape the
    traffic can form: each pow2 batch bucket up to the largest batch its
    clients can fill, with every combination of the mix's plan kinds; then
    the whole served path once per kind.  Returns the device passes run."""
    engine, cache = system.engine, system.cache
    clients = traffic.get("clients") or engine.max_batch
    buckets = pow2_buckets(min(int(clients), engine.max_batch))
    plans = {name: parse(r["text"], cache.embed_fn, cache.embeddings_for_ids, cache.lexical_fn)
             for name, r in samples.items()}
    k = [r["k"] for r in samples.values()][0]
    passes = 0
    store = cache.store
    for b in buckets:
        for combo in _combos(sorted(plans), b):
            batch = [plans[c] for c in itertools.islice(itertools.cycle(combo), b)]
            with store.lock:
                score_select_segments(system.backend, store.segments, batch, [k] * b,
                                      now=system.now, cohort=True)
            passes += 1
    for r in samples.values():
        call(system, r)
    return passes
