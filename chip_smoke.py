#!/usr/bin/env python3
"""Chip smoke test: the served retrieval path on a TPU, held to the oracle.

    python3 chip_smoke.py             # one chip: jit-jax and pallas
    python3 chip_smoke.py --chips 4   # the sharded backend across 4 chips

Drives the normal entry points at the paper's corpus sizes and compares
every answer with the ``reference-numpy`` oracle on the same data:

1. The production corpus (240,000 chunks, 4,000 sessions, seed 0) goes
   into SQLite through ``HashEmbedder(128)``.  For each device backend a
   ``RetrievalService`` answers the composed three-modulation ``vec_ops``
   statement with ``diverse``, a Phase-1 pre-filtered ``vec_ops``, a
   ``hybrid_search``, and 32 concurrent ``search()`` calls through its
   ``serving()`` batched engine.
2. A 1,000,448 x 128 corpus, tiled from the 240k matrix with seeded noise
   (512 MB in HBM), answers the composed query and one batch of 16.

With ``--chips 4`` only the sharded path runs: the 1M corpus placed
row-sharded over a four-chip mesh, compared with ``jit-jax`` on one chip
and with the oracle, and each chip's bytes in use are printed.

Every phase prints its device, compile seconds, warm latency, traces,
uploads, whether Pallas ran compiled and device bytes in use.  The last
line of standard output is ``{"ok": true, "device": {...}}``; a mismatch
or an exception exits non-zero without it, and so does a run on anything
but a TPU.  Everything runs in this one process: a chip belongs to one
process at a time.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import contextlib
import dataclasses
import json
import sqlite3
import sys
import time
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

_SRC = Path(__file__).resolve().parent / "src"
if _SRC.is_dir() and str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.backends import get_backend  # noqa: E402
from repro.core.grammar import parse  # noqa: E402
from repro.core.vectorcache import VectorCache  # noqa: E402
from repro.data.corpus import build_database, generate_corpus  # noqa: E402
from repro.embed import HashEmbedder  # noqa: E402
from repro.kernels.mmr.ops import mmr_select  # noqa: E402
from repro.kernels.pem_score.ops import pem_score  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve.engine import BatchedRetrievalEngine  # noqa: E402
from repro.serve.retrieval import RetrievalService  # noqa: E402
from repro.sqlio.schema import load_embedding_matrix  # noqa: E402

NOW = 1_770_000_000.0
DIM = 128
PAPER_CHUNKS, PAPER_SESSIONS = 240_000, 4_000
SCALE_ROWS = 1_000_448            # 977 pem_score row blocks; 4 equal shards
ORACLE = "reference-numpy"
DEVICE_BACKENDS = ("jit-jax", "pallas")
K = 10                            # results per search() call

#: Largest accepted |device - oracle| on a raw score.  Both sides compute
#: in f32 (device matmuls at HIGHEST precision) with different summation
#: orders, which moves a score by ~1e-7; 1e-5 leaves margin for that and
#: nothing for a bf16 pass (~1e-3).
SCORE_TOL = 1e-5
#: The same bound on SQL scores, which the materializer min-max normalizes
#: over the result set: normalization divides a raw difference by the
#: set's score range (~0.1 on these queries), so the bound scales with it.
SQL_TOL = 1e-4

#: the composed three-modulation query (benchmarks/pem_snapshot.py TOKENS)
TOKENS = ("similar:how the system works architecture "
          "suppress:website landing page design "
          "from:prototype sketch to:production deployment "
          "decay:30 diverse pool:500")
SQL = {
    "composed": f"SELECT v.id, v.score FROM vec_ops('{TOKENS}') v "
                "ORDER BY v.score DESC, v.id",
    "filtered": "SELECT v.id, v.score FROM vec_ops('similar:database "
                "migration schema decay:30', 'SELECT id FROM chunks WHERE "
                "type = ''assistant''') v ORDER BY v.score DESC, v.id",
    "hybrid": "SELECT v.id, v.score FROM hybrid_search('server restart "
              "lifecycle', 0.6) v ORDER BY v.score DESC, v.id",
}
_TOPICS = ("server lifecycle", "identity provenance", "rendering pipeline",
           "auth token refresh", "database migration", "storage index schema",
           "deploy pipeline rollout", "market pitch tagline")


def serving_tokens(n: int) -> List[str]:
    """``n`` distinct requests mixing plain, decay, suppress and diverse."""
    forms = ("similar:{t} v{i}", "similar:{t} v{i} decay:30",
             "similar:{t} v{i} suppress:website landing page",
             "similar:{t} v{i} diverse decay:14")
    return [forms[i % 4].format(t=_TOPICS[i % len(_TOPICS)], i=i)
            for i in range(n)]


# -- holding device answers to the oracle ------------------------------------


class Mismatch(AssertionError):
    """A device answer differs from the oracle's beyond the stated rule."""


Answer = List[Tuple[int, float]]


def check_answer(label: str, got: Answer, want: Answer, tol: float,
                 full: Optional[Answer] = None,
                 keys: Optional[Sequence[float]] = None) -> Tuple[int, float]:
    """Hold ``got`` to the oracle's ``want``; returns (swaps, max |diff|).

    The ids must match position by position.  A run of positions may hold
    the same ids in another order only where the oracle's consecutive
    ordering scores across it differ by less than ``tol`` — a near tie
    that two summation orders may break differently; each displaced id
    counts as a swap.  ``full`` extends the oracle past the answer's cut,
    so a near tie straddling the cut is told apart from a wrong row.
    Every id's score must be within ``tol`` of the oracle's.

    The ordering score is the returned score, except for an answer in MMR
    order, whose ``keys`` (:func:`mmr_keys`, aligned with ``full`` or
    ``want``) are the objective values at which the oracle made each pick:
    two neighbours may swap only where those tie, and a near tie that
    changes later picks fails, naming the two tied scores.
    """
    ext = list(full) if full is not None else list(want)
    w_ids = [int(i) for i, _ in ext]
    w_sc = np.asarray([s for _, s in ext], np.float64)
    order = w_sc if keys is None else np.asarray(keys, np.float64)
    g_ids = [int(i) for i, _ in got]
    g_sc = np.asarray([s for _, s in got], np.float64)
    n = len(g_ids)
    if n != len(want) or [int(i) for i, _ in want] != w_ids[:len(want)]:
        raise Mismatch(f"{label}: {n} rows from the device, "
                       f"{len(want)} from the oracle")
    if len(set(g_ids)) != n:
        raise Mismatch(f"{label}: the device returned an id twice")
    swaps, i = 0, 0
    while i < n:
        if g_ids[i] == w_ids[i]:
            i += 1
            continue
        j = i
        while j + 1 < len(w_ids) and abs(order[j] - order[j + 1]) < tol:
            j += 1
        end = min(j + 1, n)
        if j == i or not set(g_ids[i:end]) <= set(w_ids[i:j + 1]):
            nxt = (f"{float(order[i + 1])!r} (id {w_ids[i + 1]})"
                   if i + 1 < len(w_ids) else "none")
            raise Mismatch(
                f"{label}: position {i}: device id {g_ids[i]} score "
                f"{float(g_sc[i])!r}, oracle id {w_ids[i]} score "
                f"{float(w_sc[i])!r}; the oracle's ordering scores here "
                f"and next: {float(order[i])!r}, {nxt}")
        swaps += sum(a != b for a, b in zip(g_ids[i:end], w_ids[i:end]))
        i = end
    by_id = dict(zip(w_ids, w_sc))
    diff = max((abs(s - by_id[i]) for i, s in zip(g_ids, g_sc)), default=0.0)
    if diff > tol:
        raise Mismatch(f"{label}: a score differs by {float(diff)!r} > {tol}")
    return swaps, float(diff)


def mmr_keys(answer: Answer, embeds: np.ndarray, lam: float) -> np.ndarray:
    """The MMR objective at which the oracle made each pick of a diverse
    answer: ``lam * rel - (1 - lam) * max sim`` to the earlier picks (the
    first pick has no penalty) — the score that orders such an answer."""
    rel = np.asarray([s for _, s in answer], np.float64)
    e = np.asarray(embeds, np.float64)
    sims = e @ e.T
    keys = lam * rel
    for i in range(1, len(rel)):
        keys[i] -= (1.0 - lam) * sims[i, :i].max()
    return keys


def oracle_keys(cache: VectorCache, tokens: str,
                answer: Answer) -> Optional[np.ndarray]:
    """:func:`mmr_keys` for ``tokens``' oracle ``answer`` if the query is
    diverse, else None (its scores order it)."""
    plan = parse(tokens, cache.embed_fn, cache.embeddings_for_ids,
                 cache.lexical_fn)
    if plan.diverse is None:
        return None
    embeds = cache.embeddings_for_ids([i for i, _ in answer])
    return mmr_keys(answer, embeds, plan.diverse.lam)


# -- measurement -------------------------------------------------------------


class CompileClock:
    """XLA compile seconds (or persistent-cache retrieval) and cache hits,
    summed from JAX's monitoring events while :meth:`watch` is open."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.BACKEND_COMPILE:
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    @contextlib.contextmanager
    def watch(self) -> Iterator["CompileClock"]:
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration)
            jax.monitoring.unregister_event_listener(self._on_event)


def device_bytes_in_use() -> List[Optional[int]]:
    """``memory_stats()["bytes_in_use"]`` per local device (None where the
    backend keeps no such count)."""
    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.local_devices()]


def pallas_compiled(interpret: bool) -> bool:
    """Whether the pallas backend's kernels, with its interpret flag, lower
    to TPU custom calls — compiled Mosaic, not interpret-mode HLO."""
    f32 = jnp.float32
    spec = jax.ShapeDtypeStruct
    texts = [
        pem_score.lower(spec((1024, DIM), f32), spec((DIM, 1), f32),
                        spec((DIM, 1), f32), spec((1024,), f32),
                        interpret=interpret).compile().as_text(),
        mmr_select.lower(spec((1, 128, DIM), f32), spec((1, 128), f32), 10,
                         0.7, interpret=interpret).compile().as_text(),
    ]
    return all("tpu_custom_call" in t for t in texts)


@dataclasses.dataclass
class Record:
    """One engine's pass through one phase."""

    phase: str
    engine: str
    answers: Dict[str, Tuple[int, int, float]] = dataclasses.field(
        default_factory=dict)          # label -> (rows, swaps, max |diff|)
    first_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    warm_ms: Dict[str, float] = dataclasses.field(default_factory=dict)
    compile_s: float = 0.0
    cache_hits: int = 0
    traces: Optional[int] = None
    uploads: Optional[int] = None
    pallas_compiled: Optional[bool] = None
    bytes_in_use: List[Optional[int]] = dataclasses.field(
        default_factory=list)
    batches: Optional[int] = None

    def finish(self, backend, clock: CompileClock) -> "Record":
        self.compile_s = clock.seconds
        self.cache_hits = clock.cache_hits
        cache = getattr(backend, "plan_cache", None)
        self.traces = cache.stats()["jax_traces"] if cache else None
        dev = getattr(backend, "device_cache_stats", None)
        self.uploads = dev()["uploads"] if dev else None
        if backend.name == "pallas":
            self.pallas_compiled = pallas_compiled(backend.interpret)
        self.bytes_in_use = device_bytes_in_use()
        return self

    def lines(self) -> List[str]:
        head = f"smoke phase={self.phase} engine={self.engine}"
        out = []
        for label, (rows, swaps, diff) in self.answers.items():
            out.append(
                f"{head} answer={label} rows={rows} match=oracle "
                f"swaps={swaps} max_abs_diff={diff:.3e} "
                f"first_s={self.first_s[label]:.3f} "
                f"warm_ms_per_query={self.warm_ms[label]:.3f}")
        out.append(
            f"{head} compile_s={self.compile_s:.3f} "
            f"cache_hits={self.cache_hits} plan_cache_traces={self.traces} "
            f"uploads={self.uploads} pallas_compiled={self.pallas_compiled} "
            f"batches={self.batches} bytes_in_use={self.bytes_in_use}")
        return out


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _warm_ms(fn, repeats: int = 3) -> float:
    times = []
    for _ in range(repeats):
        _, t = _timed(fn)
        times.append(t)
    return float(np.median(times)) * 1e3


def _answer(record: Record, label: str, query, want: Answer, tol: float,
            keys: Optional[np.ndarray] = None) -> None:
    got, first = _timed(query)
    swaps, diff = check_answer(f"{record.phase}/{record.engine}/{label}",
                               got, want, tol, keys=keys)
    record.answers[label] = (len(got), swaps, diff)
    record.first_s[label] = first
    record.warm_ms[label] = _warm_ms(query)


def _concurrent(record: Record, label: str, search, tokens: Sequence[str],
                oracle_full: Dict[str, Answer],
                keys: Dict[str, Optional[np.ndarray]]) -> None:
    """All ``tokens`` at once through ``search`` from as many threads;
    each answer is held to the oracle's full answer (``keys`` order the
    diverse ones); warm time is wall per query."""
    def round_():
        with cf.ThreadPoolExecutor(len(tokens)) as ex:
            return list(ex.map(search, tokens))

    got, first = _timed(round_)
    swaps, diff = 0, 0.0
    for tok, answer in zip(tokens, got):
        full = oracle_full[tok]
        s, d = check_answer(f"{record.phase}/{record.engine}/{label}/{tok}",
                            answer, full[:K], SCORE_TOL, full, keys[tok])
        swaps, diff = swaps + s, max(diff, d)
    record.answers[label] = (len(got), swaps, diff)
    record.first_s[label] = first
    record.warm_ms[label] = _warm_ms(round_, repeats=2) / len(tokens)


# -- the phases --------------------------------------------------------------


def build_corpus(n_chunks: int, n_sessions: int,
                 seed: int = 0) -> sqlite3.Connection:
    """The paper's production corpus in SQLite (``HashEmbedder(128)``)."""
    chunks = generate_corpus(n_chunks=n_chunks, n_sessions=n_sessions,
                             seed=seed, now=NOW)
    conn = sqlite3.connect(":memory:", check_same_thread=False)
    build_database(conn, chunks, HashEmbedder(DIM))
    return conn


def tiled_corpus(conn: sqlite3.Connection, n_rows: int, seed: int = 0):
    """``n_rows`` rows tiled from the SQLite corpus, each tile after the
    first perturbed with seeded N(0, 0.05) noise and re-normalized (the
    benchmark's million-chunk corpus)."""
    _, base, ts = load_embedding_matrix(conn, DIM)
    base = base / np.linalg.norm(base, axis=1, keepdims=True)
    rng = np.random.default_rng(seed)
    mats, stamps = [], []
    for r in range(-(-n_rows // base.shape[0])):
        m = base if r == 0 else base + rng.normal(
            0, 0.05, base.shape).astype(np.float32)
        mats.append(m / np.linalg.norm(m, axis=1, keepdims=True))
        stamps.append(ts)
    matrix = np.ascontiguousarray(np.concatenate(mats)[:n_rows], np.float32)
    return np.arange(n_rows), matrix, np.concatenate(stamps)[:n_rows]


def phase_served(conn: sqlite3.Connection, engines: Sequence[str],
                 n_concurrent: int = 32) -> List[Record]:
    """SQL (composed, filtered, hybrid) and concurrent ``search()`` through
    ``RetrievalService(engine=...)`` and its ``serving()`` engine."""
    emb = HashEmbedder(DIM)
    oracle = RetrievalService(conn, dim=DIM, embedder=emb, now=NOW,
                              engine=ORACLE)
    want = {}
    for label, sql in SQL.items():
        res = oracle.flex_search(sql)
        if not res.ok:
            raise RuntimeError(f"oracle {label}: {res.error}")
        want[label] = [(int(r[0]), float(r[1])) for r in res.rows]
    tokens = serving_tokens(n_concurrent)
    full = {t: oracle.search(t, k=None) for t in tokens}
    keys = {t: oracle_keys(oracle.cache, t, full[t]) for t in tokens}

    records = []
    for name in engines:
        svc = RetrievalService(conn, dim=DIM, embedder=emb, now=NOW,
                               engine=name)
        rec = Record(f"served-{oracle.cache.store.n_rows}", name)
        clock = CompileClock()
        try:
            with clock.watch():
                for label, sql in SQL.items():
                    def query(sql=sql):
                        res = svc.flex_search(sql)
                        if not res.ok:
                            raise RuntimeError(f"{name} {sql}: {res.error}")
                        return [(int(r[0]), float(r[1])) for r in res.rows]
                    _answer(rec, label, query, want[label], SQL_TOL)
                engine = svc.serving(max_batch=32)
                _concurrent(rec, f"search_x{n_concurrent}",
                            lambda t: engine.search(t, K, timeout=900),
                            tokens, full, keys)
                rec.batches = engine.stats()["batches_served"]
        finally:
            svc.close()
        records.append(rec.finish(svc.engine, clock))
    return records


def phase_scale(conn: sqlite3.Connection, n_rows: int,
                engines: Sequence[str], batch: int = 16) -> List[Record]:
    """The composed query and one batch of ``batch`` requests over an
    ``n_rows`` tiled corpus through ``VectorCache`` and the batched
    engine, per backend in ``engines``; oracle answers computed once."""
    ids, matrix, stamps = tiled_corpus(conn, n_rows)
    emb = HashEmbedder(DIM)
    vc = VectorCache(ids, matrix, stamps, emb, normalized=True)
    want = vc.search(TOKENS, now=NOW, engine=ORACLE)
    want_keys = oracle_keys(vc, TOKENS, want)
    tokens = serving_tokens(batch)
    full = {t: vc.search(t, now=NOW, engine=ORACLE) for t in tokens}
    keys = {t: oracle_keys(vc, t, full[t]) for t in tokens}

    records = []
    for name in engines:
        backend = get_backend(name)
        rec = Record(f"tiled-{n_rows}", name)
        clock = CompileClock()
        engine = BatchedRetrievalEngine(vc, max_batch=batch, max_wait_ms=200.0,
                                        now=NOW, engine=backend)
        try:
            with clock.watch():
                _answer(rec, "composed",
                        lambda: vc.search(TOKENS, now=NOW, engine=backend),
                        want, SCORE_TOL, want_keys)
                _concurrent(rec, f"batch_x{batch}",
                            lambda t: engine.search(t, K, timeout=900),
                            tokens, full, keys)
                rec.batches = engine.stats()["batches_served"]
        finally:
            engine.close()
        records.append(rec.finish(backend, clock))
    return records


def platform_or_exit(chips: int) -> Dict[str, object]:
    """The device as JAX reports it; exits non-zero unless it is a TPU
    with at least ``chips`` chips."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found platform {platform!r}, not "
                         "a TPU; refusing to report a result")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: {len(devices)} TPU chips, "
                         f"{chips} needed")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path across four chips")
    args = ap.parse_args(argv)
    device = platform_or_exit(args.chips)
    cache_dir = enable_compile_cache()
    print(f"smoke device platform={device['platform']} "
          f"kind={device['kind']!r} count={device['count']} "
          f"compile_cache={cache_dir}", flush=True)

    t0 = time.perf_counter()
    conn = build_corpus(PAPER_CHUNKS, PAPER_SESSIONS)
    print(f"smoke corpus chunks={PAPER_CHUNKS} sessions={PAPER_SESSIONS} "
          f"build_s={time.perf_counter() - t0:.1f}", flush=True)

    if args.chips == 4:
        phases = [lambda: phase_scale(conn, SCALE_ROWS,
                                      ("sharded", "jit-jax"))]
    else:
        phases = [lambda: phase_served(conn, DEVICE_BACKENDS),
                  lambda: phase_scale(conn, SCALE_ROWS, DEVICE_BACKENDS)]
    for run in phases:
        for rec in run():
            for line in rec.lines():
                print(line, flush=True)
            if rec.engine == "pallas" and not rec.pallas_compiled:
                raise Mismatch("the pallas backend did not run compiled")
    print(f"smoke total_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
