"""``flex_search(sql)`` statements through the deployment's SQL surface.

A mix entry with ``hybrid`` becomes ``hybrid_search('<keyword>', W)``; any
other becomes ``vec_ops('<tokens>'[, '<Phase-1 SELECT>'])``.  Every
statement orders ``v.score DESC, v.id``; its answer is the ``(id, score)``
rows.  Needs a deployment with ``svc`` (a ``RetrievalService``), ``conn``
(its SQLite connection) and ``cfg``.
"""

from __future__ import annotations

import asyncio
from typing import Dict

import numpy as np

from repro.core.backends import PrefilterRouter, score_select_prefiltered
from repro.core.grammar import parse

from perfbench.lib.traffic import base_spec, modulated, num, text

SURFACE = "sql"
ORDER = "ORDER BY v.score DESC, v.id"
#: filtered statements served per Phase-1 predicate while warming: the
#: service's router learns its masked/gather crossover from the passes it
#: times, and starts from a static guess until each arm has five
ROUTER_WARM_ROUNDS = 3


class SqlError(RuntimeError):
    """A statement came back as a structured failure."""


def make(rng, entry: dict, traffic: dict) -> dict:
    if entry.get("hybrid") is not None:
        spec = base_spec(entry, SURFACE, None)
        spec["similar"] = spec["keyword"] = text(rng, entry["keyword"])
        spec["text"] = (f"SELECT v.id, v.score FROM hybrid_search('{spec['keyword']}', "
                        f"{num(spec['hybrid'])}) v {ORDER}")
        return spec
    spec = modulated(rng, entry, SURFACE, None)
    pre = ""
    if spec["filter"]:
        pred = " AND ".join(f"{c} = ''{v}''" for c, v in sorted(spec["filter"].items()))
        pre = f", 'SELECT id FROM chunks WHERE {pred}'"
    spec["text"] = f"SELECT v.id, v.score FROM vec_ops('{spec['tokens']}'{pre}) v {ORDER}"
    return spec


def call(system, spec: dict) -> list:
    res = system.svc.flex_search(spec["text"])
    if not res.ok:
        raise SqlError(res.error)
    return [(int(r[0]), float(r[1])) for r in res.rows]


async def submit(system, spec: dict) -> list:
    return await asyncio.to_thread(call, system, spec)


def _pred(pred: dict) -> str:
    return " AND ".join(f"{c} = '{v}'" for c, v in sorted(pred.items()))


def _with_filter(entry: dict, traffic: dict, pred: dict) -> dict:
    """The mix entry's statement with one given Phase-1 predicate."""
    return make(np.random.default_rng(0), dict(entry, filter=[pred]), traffic)


def warm(system, traffic: dict, samples: Dict[str, dict]) -> int:
    """Every statement kind through ``flex_search``; each Phase-1 filter
    of the mix on both router arms (masked device pass and host gather),
    since the service's router learns its crossover while it serves; and
    a hybrid statement with no keyword hit (no lexical bias).  Then the
    filtered statements again, so the router's learned crossover starts
    the window from a few rounds of timings rather than from none."""
    passes = 0
    cache = system.svc.cache
    store = cache.store
    for entry in traffic["mix"]:
        base = samples[entry["name"]]
        for _ in range(2):
            call(system, base)
            passes += 1
        for pred in entry.get("filter") or ():
            spec = _with_filter(entry, traffic, pred)
            plan = parse(spec["tokens"], cache.embed_fn, cache.embeddings_for_ids,
                         cache.lexical_fn)
            cand = [r[0] for r in
                    system.conn.execute(f"SELECT id FROM chunks WHERE {_pred(pred)}").fetchall()]
            for threshold in (0.0, 1.0):  # masked arm, then gather arm
                with store.lock:
                    score_select_prefiltered(
                        system.svc.engine, store, store.segments, [plan], [plan.pool],
                        cand, now=float(system.cfg["now"]),
                        router=PrefilterRouter(mask_threshold=threshold, adaptive=False))
                passes += 1
        for _ in range(ROUTER_WARM_ROUNDS):
            for pred in entry.get("filter") or ():
                call(system, _with_filter(entry, traffic, pred))
                passes += 1
        if entry.get("hybrid") is not None:
            miss = dict(base, keyword="zzzzunmatched")
            miss["text"] = base["text"].replace(f"'{base['keyword']}'", "'zzzzunmatched'")
            call(system, miss)
            passes += 1
    return passes
