"""ExecutionBackend — the single engine-dispatch seam (all Phase-2 paths).

Before this module, engine selection was three divergent mechanisms:
string dispatch inside ``VectorCache.search_plan``, hand-rolled fused
matmuls in ``BatchedRetrievalEngine._serve``, and pass-through strings in
``Materializer``/``RetrievalService``.  Now every consumer resolves a
backend from ONE registry and calls the same primitives:

    score(matrix, days_ago, plan)                    -> (N,)   one request
    score_panel(matrix, days_ago, plans)             -> (N, B) a micro-batch
    score_select(matrix, days_ago, plans, ks, mask=) -> per-plan top candidates
    score_select_segments(backend, segments, ...)    -> segmented corpus driver
    score_select_prefiltered(backend, store, ...)    -> Phase-1 filtered driver
                                                        (masked-device vs
                                                        gather-host router)
    score_select_filter_panel(backend, store, ...)   -> heterogeneous-filter
                                                        batch via one (N, B)
                                                        mask panel

``score_select`` is the fused score->select stage: it returns ONLY the
top-:func:`selection_width` candidate ``(indices, scores)`` per plan, so
device backends never ship the full (N, B) score panel back to the host —
just (pool,)-sized candidate lists cross the device boundary (Bruch,
*Foundations of Vector Retrieval*: selection-fused scoring is the standard
trick for exact search at scale).  On the device backends the chain now
covers diversity too (:class:`_DeviceMMRMixin`): MMR runs over the
oversampled pool IN the compiled graph (jit-jax/sharded) or through the
``kernels/mmr`` pallas chain, so diverse plans return only the final k and
the pool never crosses the device boundary.  The host finishing stage
(:func:`finalize_candidates`: truncate, or the :func:`mmr_host` oracle over
the oversampled pool) is shared by every host-path consumer, so batched and
direct paths rank identically — device MMR is pinned bit-identical to it.

Registered backends:

    reference-numpy  paper-faithful, one matvec per direction (Table 1)
    fused-numpy      folded two-matvec formulation (one corpus stream)
    jit-jax          fused formulation jitted through XLA + device top-k
    pallas           fused TPU scoring kernel -> jax.lax.top_k on the
                     device-resident panel (no host hop between score
                     and select)
    sharded          shard_map row-sharded scoring, shard-local top-k +
                     union merge (repro.dist.pem_sharded contract)

The numpy backends keep the host path (full panel + numpy selection) so the
equivalence suites (tests/test_backends.py, tests/test_score_select.py)
stay anchored to the reference oracle.  Device backends compile through a
:class:`PlanCache` (LRU-bounded) keyed on :class:`PlanStructure` — plan
*shape* (batch width, decay present/absent, suppress count bucketed by
padding, top-k width bucketed to powers of two, corpus row count padded
to its row grid) — so distinct query texts with the same structure never
retrigger tracing, and a stream of varying corpus/segment sizes compiles
one graph per grid step, not one per exact row count.

Live corpora (`repro.core.segments`) score through
:func:`score_select_segments`: each segment scores independently (its
tombstones masked to -inf ON DEVICE via ``score_select``'s ``mask``
argument, before selection), per-segment top-k candidates merge on the
host exactly like ``dist/pem_sharded.union_merge_topk`` merges per-shard
candidates, and the result is bit-identical to a monolithic store.  The
per-array device matrix cache (:class:`_DeviceMatrixMixin`) holds one
entry per warm segment, so appending a segment uploads ONLY the delta.

All backends are algebraically identical on the composed plan grammar.
Later scaling PRs (multi-host, async, cache tiering) plug in here via
:func:`register_backend`.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from typing import (Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import numpy as np

from repro.core import modulations as M
from repro.core.spans import RECORDER

__all__ = [
    "ExecutionBackend",
    "PlanCache",
    "PlanStructure",
    "get_backend",
    "register_backend",
    "list_backends",
    "select_candidates",
    "selection_width",
    "finalize_candidates",
    "score_select_segments",
    "score_select_cohort",
    "score_select_prefiltered",
    "score_select_filter_panel",
    "finalize_segment_candidates",
    "PrefilterRouter",
    "FusedCounters",
    "mmr_host",
    "plan_fusion_bias",
    "fusion_bias_arrays",
    "finalize_fusion",
]

Candidates = Tuple[np.ndarray, np.ndarray]  # (indices, scores), descending


def _require_days(plan: M.ModulationPlan, days_ago: Optional[np.ndarray]) -> None:
    if plan.decay is not None and days_ago is None:
        raise ValueError("decay: modulation requires per-chunk timestamps")


def _decay_column(days_ago: np.ndarray, half_life: float) -> np.ndarray:
    return 1.0 / (1.0 + days_ago / half_life)


def _pow2_bucket(x: int) -> int:
    """0 for x<=0, else the next power of two >= x (trace-bounding pad)."""
    if x <= 0:
        return 0
    return 1 << (x - 1).bit_length()


#: the device row grid doubles up to this many rows, then grows in steps
#: of ``ROW_GRID_STEP`` (a multiple of 4 shards x 8 sublanes)
ROW_GRID_POW2_MAX = 1 << 20
ROW_GRID_STEP = 1 << 17


def _row_grid(n_rows: int) -> int:
    """The device row count a corpus of ``n_rows`` pads to: the pow2
    bucket at or below ``ROW_GRID_POW2_MAX`` rows (segments and Phase-1
    sub-corpora of every size share a few traces), above it the next
    multiple of ``ROW_GRID_STEP`` (8,841,823 rows pad to 8,912,896, not
    to 16,777,216: the padding is zeros that every scan reads)."""
    if n_rows <= ROW_GRID_POW2_MAX:
        return max(_pow2_bucket(n_rows), 1)
    return -(-n_rows // ROW_GRID_STEP) * ROW_GRID_STEP


def _half_lives(plans: Sequence[M.ModulationPlan]) -> np.ndarray:
    """Per-plan half-life column; inf makes the decay factor exactly 1.0."""
    return np.asarray(
        [p.decay.half_life_days if p.decay is not None else np.inf
         for p in plans],
        dtype=np.float32,
    )


def _days_f32(days_ago: Optional[np.ndarray], n: int) -> np.ndarray:
    return (np.zeros(n, np.float32) if days_ago is None
            else np.asarray(days_ago, np.float32))


def _empty_candidates() -> Candidates:
    return np.empty(0, np.int64), np.empty(0, np.float32)


def _slice_candidates(idx, vals, widths: Sequence[int]) -> List[Candidates]:
    """Host tail shared by every device ``score_select``: fetch the
    (B, width) blocks — the ONLY device->host copy — and slice each plan's
    prefix (rows are sorted descending, so the first w are its top-w)."""
    idx = np.asarray(idx)
    vals = np.asarray(vals)
    return [(idx[j, :w].astype(np.int64), vals[j, :w])
            for j, w in enumerate(widths)]


def mmr_host(
    pool_embeds: np.ndarray,
    pool_scores: np.ndarray,
    k: int,
    lam: float,
) -> np.ndarray:
    """Host MMR over an oversampled candidate pool -> selection positions.

    THE oracle every fused device-MMR path (:class:`_DeviceMMRMixin`, the
    ``kernels/mmr`` pallas chain) is pinned bit-identical against, and the
    fallback the numpy backends keep.  The single call site of
    ``modulations.mmr_select_np`` — :func:`finalize_candidates` and
    :func:`select_candidates` both finish diversity here.
    """
    return M.mmr_select_np(pool_embeds, pool_scores, k, lam)


@dataclasses.dataclass
class FusedCounters:
    """Fused-Phase-2 observability (``RetrievalService.stats()["fused"]``).

    ``device_mmr`` counts diverse plans finished by on-device MMR — the
    oversample pool never crossed to the host.  ``host_pool_transfers``
    counts diverse plans that DID ship their pool back for the
    :func:`mmr_host` oracle; a regression back to host MMR shows up here
    before it shows up as latency.  ``panel_batches`` counts batched
    (N, B) mask-panel passes that served a heterogeneous-filter cohort in
    ONE device scoring pass instead of one per distinct filter.
    ``upload_bytes`` sums the host arrays every device ``score_select``
    hands its compiled graph (``days``, the live mask or mask panel, the
    bias panel, the query panels, and a corpus matrix not yet resident):
    the per-query host-to-device traffic.  Benign int bumps, same
    convention as the store's counters.
    """

    device_mmr: int = 0
    host_pool_transfers: int = 0
    panel_batches: int = 0
    upload_bytes: int = 0

    def stats(self) -> Dict[str, int]:
        return {
            "device_mmr": self.device_mmr,
            "host_pool_transfers": self.host_pool_transfers,
            "panel_batches": self.panel_batches,
            "upload_bytes": self.upload_bytes,
        }


def _count_uploads(counters: Optional[FusedCounters], *arrays) -> None:
    """Add the host arrays among ``arrays`` to ``counters.upload_bytes``."""
    if counters is not None:
        counters.upload_bytes += sum(a.nbytes for a in arrays
                                     if isinstance(a, np.ndarray))


# every device matmul runs at full f32 precision: a TPU's default rounds
# f32 operands to bf16, which would change rankings, not just speed them up
_HIGHEST = "highest"

# -1e30 stands in for -inf inside traced MMR bodies (0 * -inf is NaN; the
# kernels/mmr chain uses the same sentinel, see kernels/mmr/kernel.py NEG)
_MMR_NEG = -1e30


def _device_mmr_trace(emb, rel, lams, pool_w, k: int):
    """Traced batched MMR over a top-k pool (pure ``jax.lax``, runs inside
    any jitted graph — the portable equivalent of the pallas kernel).

    ``emb`` (B, W, d) pool embeddings, ``rel`` (B, W) relevance descending,
    ``lams`` (B,) per-plan lambda — 1.0 is PURE relevance, whose greedy
    selection is the identity permutation, so non-diverse columns ride the
    same graph unchanged — and ``pool_w`` (B,) TRUE pool widths: positions
    past them (static-width padding, -inf masked slots) pin to the NEG
    sentinel and can never be argmaxed while real rows remain.  Returns
    (B, k) int32 selection positions, the same greedy argmax of
    ``lam*rel - (1-lam)*max_sim`` as :func:`mmr_host` with matching
    first-occurrence tie-breaking.
    """
    import jax
    import jax.numpy as jnp

    bsz, w, _ = emb.shape
    iota = jnp.arange(w)[None, :]
    rel = jnp.maximum(rel, _MMR_NEG)  # -inf -> sentinel: 0*rel stays finite
    valid = iota < pool_w[:, None]
    rel = jnp.where(valid, rel, _MMR_NEG)

    # precompute the pool gram matrix ONCE: the loop body then gathers a
    # row of S instead of running two (W, d) einsums per pick — one big
    # matmul replaces 2k tiny ones (>20x on the k=500 headline pool)
    S = jnp.einsum("bwd,bvd->bwv", emb, emb, precision=_HIGHEST)

    def body(i, carry):
        max_sim, taken, out = carry
        penalty = jnp.where(max_sim <= _MMR_NEG * 0.5, 0.0, max_sim)
        mmr = lams[:, None] * rel - (1.0 - lams[:, None]) * penalty
        # mask invalid slots AFTER the blend: lam=0 zeroes the rel term,
        # so padded positions need an unconditional NEG, not just NEG rel
        mmr = jnp.where(jnp.logical_and(valid, ~taken), mmr, _MMR_NEG)
        j = jnp.argmax(mmr, axis=1)
        sim = jnp.take_along_axis(S, j[:, None, None], axis=1)[:, 0, :]
        max_sim = jnp.maximum(max_sim, sim)
        taken = jnp.logical_or(taken, iota == j[:, None])
        out = out.at[:, i].set(j.astype(jnp.int32))
        return max_sim, taken, out

    init = (jnp.full((bsz, w), _MMR_NEG, jnp.float32),
            jnp.zeros((bsz, w), bool),
            jnp.zeros((bsz, k), jnp.int32))
    _, _, out = jax.lax.fori_loop(0, k, body, init)
    return out


def _panel_inputs(plans, structure: "PlanStructure", use_mmr: bool):
    """Runtime panel inputs padded to ``structure.batch`` — a panel
    structure pow2-buckets the batch, so padded columns carry zero
    queries / inf half-life / lam 1.0 and slice away on the host."""
    q_pre, q_sup = M.fold_plans(plans)
    half = _half_lives(plans)
    lams = np.asarray(
        [float(p.diverse.lam) if (use_mmr and p.diverse is not None) else 1.0
         for p in plans], np.float32)
    bpad = structure.batch - len(plans)
    if bpad:
        q_pre = np.pad(q_pre, ((0, 0), (0, bpad)))
        q_sup = np.pad(q_sup, ((0, 0), (0, bpad)))
        half = np.pad(half, (0, bpad), constant_values=np.inf)
        lams = np.pad(lams, (0, bpad), constant_values=1.0)
    return q_pre, q_sup, half, lams


def _expand_bias(
    score_bias: np.ndarray, n_rows: int, batch: int, nplans: int
) -> np.ndarray:
    """Canonical (n_rows, batch) float32 additive-bias panel for the
    device callers: a shared (n,) bias broadcasts across plans, an (n, B)
    panel keeps its columns; row/batch padding is zero (no-op bias)."""
    b = np.asarray(score_bias, np.float32)
    if b.ndim == 1:
        b = np.repeat(b[:, None], nplans, axis=1)
    out = np.zeros((n_rows, batch), np.float32)
    out[:b.shape[0], :b.shape[1]] = b
    return out


def _pool_widths(widths, mask, n: int, batch: int) -> np.ndarray:
    """Per-plan TRUE pool widths (padded to ``batch``): each plan's
    selection width clamped to its eligible-row count, so static top-k
    padding and -inf masked slots can never enter a fused-MMR pool."""
    if mask is None:
        live = np.full(len(widths), n, dtype=np.int64)
    elif mask.ndim == 2:
        live = np.count_nonzero(mask, axis=0).astype(np.int64)
    else:
        live = np.full(len(widths), int(np.count_nonzero(mask)),
                       dtype=np.int64)
    pw = np.minimum(np.asarray(widths, np.int64), live)
    if batch > len(widths):
        pw = np.pad(pw, (0, batch - len(widths)))
    return pw.astype(np.int32)


# ---------------------------------------------------------------------------
# Plan structure + compiled-plan cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanStructure:
    """The trace-relevant *shape* of a scoring micro-batch.

    Two batches with the same structure lower to the same specialized
    graph: query texts, embedding values, and half-life magnitudes are
    runtime data, never trace constants.  Suppress count and top-k width
    are bucketed (padded up to powers of two) and the corpus row count is
    padded to its row grid (:func:`_row_grid`), so the number of distinct
    traces stays bounded as requests — and segment sizes — vary.
    """

    batch: int            # B — number of plans folded into the panel
    n_rows: int           # DEVICE row count: the corpus rows' row grid
    has_decay: bool       # decay factor branch present in the graph
    suppress_bucket: int  # max suppress count, padded to a power of two
    width: int            # static top-k width (pow2-bucketed, <= n_rows)
    mmr_k: int = 0        # in-graph MMR step count (pow2; 0 = no MMR tail)
    panel: bool = False   # (N, B) per-plan mask panel; batch pow2-bucketed
    bias: bool = False    # additive (N, B) score-bias panel (hybrid fusion)

    # NOTE on suppress_bucket: with the folded (q_pre, q_sup) formulation
    # only 0-vs-nonzero changes the lowered graph (the second matmul drops
    # out); the pow2 buckets keep the key future-proof for unfused panel
    # formulations where the direction count IS a shape.  NOTE on n_rows:
    # it is the ROW GRID (:func:`_row_grid`) — device backends zero-pad
    # the corpus up to it and mask the padding to -inf, so Phase-1
    # pre-filtered sub-corpora and store segments of varying size share
    # one compiled executable per grid step instead of one per exact row
    # count (the per-segment PlanCache would otherwise grow with every
    # append).  Up to 2^20 rows the grid is the pow2 bucket; above it a
    # doubling would leave up to half of device memory and of every scan
    # to padding, so the grid grows in 2^17-row steps there.

    # NOTE on mmr_k/panel: the diverse-on-device tail (a fori_loop of
    # mmr_k steps) and the 2-D mask panel change the lowered graph, so
    # both are structural.  mmr_k pow2-buckets the requested k and batch
    # pow2-buckets the panel width, so a stream of varying diverse ks /
    # cohort sizes compiles one graph per bucket — neither path retraces
    # per query.

    @classmethod
    def of(
        cls,
        plans: Sequence[M.ModulationPlan],
        widths: Sequence[int],
        n_rows: int,
        *,
        ks: Optional[Sequence[int]] = None,
        device_mmr: bool = False,
        panel: bool = False,
        bias: bool = False,
        cohort: bool = False,
    ) -> "PlanStructure":
        """``cohort=True`` pow2-buckets the BATCH axis even without a
        mask panel — the multi-query cohort path's trace bound: a stream
        of varying admitted-batch sizes (Q = 3, then 5, then 4 ...) pads
        into pow2 query-panel buckets and compiles one graph per bucket
        instead of one per Q (padded columns carry zero queries and are
        never sliced out into results)."""
        max_sup = max((len(p.suppress) for p in plans), default=0)
        w = max(widths, default=0)
        bucket = _row_grid(n_rows)
        width = min(max(_pow2_bucket(w), 1), bucket)
        mmr_k = 0
        if device_mmr and ks is not None and any(
                p.diverse is not None for p in plans):
            k_max = max((min(max(k, 0), n_rows) for k in ks), default=0)
            mmr_k = min(max(_pow2_bucket(k_max), 1), width)
        return cls(
            batch=(max(_pow2_bucket(len(plans)), 1) if (panel or cohort)
                   else len(plans)),
            n_rows=bucket,
            has_decay=any(p.decay is not None for p in plans),
            suppress_bucket=_pow2_bucket(max_sup),
            width=width,
            mmr_k=mmr_k,
            panel=panel,
            bias=bias,
        )


class PlanCache:
    """Compiled executables keyed on plan STRUCTURE, not plan content.

    Device backends lower one specialized graph per :class:`PlanStructure`;
    distinct query texts with the same shape hit the cache and never
    retrigger tracing, while a genuinely new shape (e.g. a new
    suppress-count bucket) builds — and traces — exactly once.

    ``jax_traces`` is incremented from INSIDE the traced python bodies, so
    it counts real (re)traces, not just cache misses; tests use it to pin
    the zero-retrace contract.  With the span recorder on, the first call
    of a freshly built executable (its trace and compile) records a
    ``plan.compile`` span.

    The cache is bounded with LRU eviction at ``maxsize``: every hit
    refreshes the entry, so the hot segments' executables stay resident no
    matter how many one-off shapes (odd pre-filter buckets, a burst of
    small delta segments) stream past.  Counters surface through
    ``RetrievalService`` stats via :meth:`stats`.
    """

    def __init__(
        self,
        builder: Callable[[PlanStructure], Callable],
        maxsize: int = 64,
    ) -> None:
        self._builder = builder
        self._fns: "OrderedDict[PlanStructure, Callable]" = OrderedDict()
        self._lock = threading.Lock()
        self.maxsize = maxsize
        self.builds = 0      # cache misses (specialized graphs built)
        self.hits = 0        # cache hits (no build, no trace)
        self.evictions = 0   # LRU evictions (bounded executable retention)
        self.jax_traces = 0  # actual traces, counted from traced bodies

    def get(self, structure: PlanStructure) -> Callable:
        with self._lock:
            fn = self._fns.get(structure)
            if fn is not None:
                self._fns.move_to_end(structure)
                self.hits += 1
                return fn
            self.builds += 1
            fn = self._fns[structure] = self._first_call(
                structure, self._builder(structure))
            while len(self._fns) > self.maxsize:
                self._fns.popitem(last=False)
                self.evictions += 1
            return fn

    def _first_call(self, structure: PlanStructure,
                    fn: Callable) -> Callable:
        """``fn`` behind a wrapper that spans its first call (the compile)
        and then puts ``fn`` itself back in the cache."""
        def first(*args):
            with self._lock:
                if self._fns.get(structure) is first:
                    self._fns[structure] = fn
            with RECORDER.span("plan.compile"):
                return fn(*args)

        return first

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._fns),
                "hits": self.hits,
                "builds": self.builds,
                "evictions": self.evictions,
                "jax_traces": self.jax_traces,
            }

    def __len__(self) -> int:
        return len(self._fns)


class _DeviceMatrixMixin:
    """Per-array device-resident corpus cache (bounded, LRU).

    A segmented store scores one matmul per segment, so the cache holds
    SEVERAL resident arrays at once — keyed on array identity + row
    padding — instead of a single slot: appending a 10k-chunk segment to
    a warm 240k corpus uploads ONLY the new segment while every sealed
    segment stays device-resident.  ``uploads`` counts host->device
    copies; tests pin the only-the-delta ingest contract on it.
    :meth:`device_cache_stats` also gives ``rows_placed``, the true rows
    of the resident arrays, and ``row_grid``, the device rows that hold
    them (padding included, across the mesh for the sharded backend).
    """

    _DEV_CACHE_SIZE = 32

    uploads = 0        # host->device copies performed
    dev_hits = 0       # calls served from the resident cache
    dev_evictions = 0  # LRU evictions

    def _place(self, mat: np.ndarray, pad: int):
        """Host -> device copy of one corpus matrix with ``pad`` zero rows
        below it; the sharded backend overrides this to place each row
        slice on its own device."""
        import jax

        return jax.device_put(np.pad(mat, ((0, pad), (0, 0))) if pad else mat)

    def _device_matrix(self, matrix: np.ndarray, pad: int = 0,
                       counters: Optional[FusedCounters] = None):
        cache: "OrderedDict[Tuple[int, int], Tuple[np.ndarray, object]]"
        cache = self.__dict__.setdefault("_dev_cache", OrderedDict())
        key = (id(matrix), pad)
        entry = cache.get(key)
        # the stored source reference guards against id() reuse after gc
        if entry is not None and entry[0] is matrix:
            cache.move_to_end(key)
            self.dev_hits += 1
            return entry[1]
        mat = np.asarray(matrix, np.float32)
        dev = self._place(mat, pad)
        if counters is not None:
            counters.upload_bytes += (mat.shape[0] + pad) * mat.shape[1] * mat.itemsize
        cache[key] = (matrix, dev)
        cache.move_to_end(key)
        self.uploads += 1
        while len(cache) > self._DEV_CACHE_SIZE:
            cache.popitem(last=False)
            self.dev_evictions += 1
        return dev

    def _any_device_matrix(self, matrix: np.ndarray):
        """Any resident device copy of ``matrix``, regardless of its row
        padding (padded rows are zero and never indexed below the true row
        count), else a fresh unpadded upload.  The merged-pool MMR gather
        reuses whatever the scoring pass left resident instead of
        re-uploading the segment under a different pad key."""
        cache = self.__dict__.get("_dev_cache")
        if cache:
            for (mid, _pad), (src, dev) in cache.items():
                if mid == id(matrix) and src is matrix:
                    self.dev_hits += 1
                    return dev
        return self._device_matrix(matrix)

    def device_cache_stats(self) -> Dict[str, int]:
        resident = list(self.__dict__.get("_dev_cache", {}).values())
        return {
            "entries": len(resident),
            "uploads": self.uploads,
            "hits": self.dev_hits,
            "evictions": self.dev_evictions,
            "rows_placed": sum(int(src.shape[0]) for src, _ in resident),
            "row_grid": sum(int(dev.shape[0]) for _, dev in resident),
        }


class _DeviceMMRMixin:
    """Fused on-device MMR for diverse plans (the jax backends).

    Inside ``score_select`` the compiled graph chains
    :func:`_device_mmr_trace` (jit-jax/sharded) or the ``kernels/mmr``
    pallas kernel after top-k, so diverse plans return only the final k
    ``(indices, scores)`` — the oversample pool never crosses the device
    boundary.  For the merged per-segment pool,
    :meth:`mmr_pool_segments` gathers the pool embeddings ON DEVICE from
    the warm resident segment matrices and runs a cached jitted MMR loop
    (pow2-bucketed pool and k, so a stream of varying pool sizes compiles
    a bounded set of graphs).  Every path is pinned bit-identical to the
    :func:`mmr_host` oracle: same greedy argmax, same first-occurrence
    tie-breaking, and the returned scores are the RELEVANCE scores at the
    selected positions (exactly what the host finishing stage returns).
    """

    device_mmr = True
    _MMR_POOL_FNS = 16  # cached merged-pool executables (pow2 buckets)

    def _use_mmr(self, plans, fused_mmr: Optional[bool]) -> bool:
        if not (self.device_mmr if fused_mmr is None else bool(fused_mmr)):
            return False
        return any(p.diverse is not None for p in plans)

    def _pool_mmr_fn(self, pool_bucket: int, k_stat: int):
        import jax

        cache = self.__dict__.setdefault("_mmr_pool_cache", OrderedDict())
        key = (pool_bucket, k_stat)
        fn = cache.get(key)
        if fn is None:
            def pool_mmr(emb, rel, lams, pool_w):
                return _device_mmr_trace(emb, rel, lams, pool_w, k_stat)

            fn = cache[key] = jax.jit(pool_mmr)
            while len(cache) > self._MMR_POOL_FNS:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return fn

    def _gather_pool_device(self, segments, gidx: np.ndarray):
        """Device-resident (pool, d) embeddings for merged global rows,
        gathered segment-by-segment from the warm resident matrices and
        un-permuted back to merged-pool order."""
        import jax.numpy as jnp

        from repro.core.segments import segment_offsets

        off = segment_offsets(segments)
        seg_idx = np.searchsorted(off, gidx, side="right") - 1
        local = gidx - off[seg_idx]
        order = np.argsort(seg_idx, kind="stable")
        parts = []
        for s in np.unique(seg_idx):
            rows = local[order[seg_idx[order] == s]]
            parts.append(jnp.take(
                self._any_device_matrix(segments[s].matrix),
                jnp.asarray(rows), axis=0))
        emb = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
        return jnp.take(emb, jnp.asarray(np.argsort(order, kind="stable")),
                        axis=0)

    def mmr_pool_segments(self, segments, gidx, vals, k: int, lam: float):
        """Device MMR over a MERGED candidate pool (the union-merged
        global rows + scores from the per-segment two-stage shape).
        Returns selection positions into the pool, host int64 —
        bit-identical to ``mmr_host(gather_rows(segments, gidx), vals,
        k, lam)`` without the pool embeddings ever leaving the device.
        """
        pool = int(gidx.size)
        k = max(0, min(int(k), pool))
        if k == 0:
            return np.empty(0, np.int64)
        import jax.numpy as jnp

        emb = self._gather_pool_device(segments,
                                       np.asarray(gidx, np.int64))
        bucket = max(_pow2_bucket(pool), 1)
        k_stat = min(max(_pow2_bucket(k), 1), bucket)
        if bucket != pool:
            emb = jnp.pad(emb, ((0, bucket - pool), (0, 0)))
        rel = np.zeros(bucket, np.float32)
        rel[:pool] = vals
        fn = self._pool_mmr_fn(bucket, k_stat)
        sel = fn(emb[None], rel[None], np.asarray([lam], np.float32),
                 np.asarray([pool], np.int32))
        return np.asarray(sel)[0, :k].astype(np.int64)

    def mmr_pool_segments_batch(self, segments, pools, ks, lams):
        """One padded device call for a COHORT of merged diverse pools.

        ``pools`` is a list of per-plan ``(gidx, vals)`` merged unions,
        ``ks``/``lams`` the matching final counts and MMR lambdas.  Every
        pool pads to the cohort's shared pow2 bucket and the whole (B,
        bucket, d) stack runs through ONE cached ``_pool_mmr_fn``
        executable — one device sync for the batch instead of one per
        diverse plan.  Per-plan results are bit-identical to serial
        :meth:`mmr_pool_segments` calls: the MMR trace is batched over
        independent rows, and pow2 padding never changes a gram dot
        product (the contraction dim is untouched).  Returns per-plan
        selection-position arrays (empty for k == 0 pools).
        """
        import jax.numpy as jnp

        sizes = [int(g.size) for g, _ in pools]
        ks = [max(0, min(int(k), s)) for k, s in zip(ks, sizes)]
        live = [j for j, (s, k) in enumerate(zip(sizes, ks)) if s and k]
        out = [np.empty(0, np.int64)] * len(pools)
        if not live:
            return out
        bucket = max(_pow2_bucket(max(sizes[j] for j in live)), 1)
        k_stat = min(max(_pow2_bucket(max(ks[j] for j in live)), 1), bucket)
        embs, rel = [], np.zeros((len(live), bucket), np.float32)
        for row, j in enumerate(live):
            gidx, vals = pools[j]
            emb = self._gather_pool_device(segments,
                                           np.asarray(gidx, np.int64))
            if bucket != sizes[j]:
                emb = jnp.pad(emb, ((0, bucket - sizes[j]), (0, 0)))
            embs.append(emb)
            rel[row, :sizes[j]] = vals
        fn = self._pool_mmr_fn(bucket, k_stat)
        sel = np.asarray(fn(
            jnp.stack(embs), rel,
            np.asarray([lams[j] for j in live], np.float32),
            np.asarray([sizes[j] for j in live], np.int32)))
        for row, j in enumerate(live):
            out[j] = sel[row, :ks[j]].astype(np.int64)
        return out


# ---------------------------------------------------------------------------
# The backend contract
# ---------------------------------------------------------------------------


class ExecutionBackend:
    """One Phase-2 scoring implementation.

    Subclasses implement :meth:`score_panel`; :meth:`score` defaults to the
    single-column case.  :meth:`score_select` is the fused score->select
    stage — the base implementation is the host path (full panel + numpy
    top-k), which the numpy backends keep so everything stays anchored to
    the reference oracle; device backends override it to select on device
    and return only (pool,)-sized candidate arrays to the host.
    """

    name: str = "?"
    #: True when the backend finishes diverse plans with on-device MMR
    #: inside its fused chain — diverse plans then return the FINAL k, not
    #: the oversample pool (see :class:`_DeviceMMRMixin`)
    device_mmr: bool = False

    def score(
        self,
        matrix: np.ndarray,
        days_ago: Optional[np.ndarray],
        plan: M.ModulationPlan,
    ) -> np.ndarray:
        return self.score_panel(matrix, days_ago, [plan])[:, 0]

    def score_panel(
        self,
        matrix: np.ndarray,
        days_ago: Optional[np.ndarray],
        plans: Sequence[M.ModulationPlan],
    ) -> np.ndarray:
        raise NotImplementedError

    def score_select(
        self,
        matrix: np.ndarray,
        days_ago: Optional[np.ndarray],
        plans: Sequence[M.ModulationPlan],
        ks: Sequence[int],
        *,
        mask: Optional[np.ndarray] = None,
        fused_mmr: Optional[bool] = None,
        score_bias: Optional[np.ndarray] = None,
        cohort: bool = False,
        counters: Optional[FusedCounters] = None,
    ) -> List[Candidates]:
        """Fused score->select: per-plan ``(indices, scores)`` of the top
        ``selection_width(plan, k, N)`` candidates, descending by score.

        ``counters`` receives the host-to-device bytes a device backend
        hands its graph (``FusedCounters.upload_bytes``); the host path
        uploads nothing.

        ``cohort=True`` marks a multi-query cohort call (several admitted
        queries folded into one panel): device backends pow2-bucket the
        batch axis of their :class:`PlanStructure` key so a stream of
        varying cohort sizes compiles one graph per bucket instead of one
        per Q.  The host path has no compiled executables to bucket, so
        the flag is accepted (one signature everywhere) and ignored.

        ``score_bias`` is an optional additive score panel — (N,) shared
        by every plan or (N, B) per-plan — added to the modulated scores
        ON DEVICE before masking and selection (the hybrid lexical leg:
        sparse ``(1-w) * minmax(bm25)`` values, zero elsewhere).  Diverse
        plans run MMR over the BIASED relevance, so fusion happens before
        selection on every path.

        ``ks[j]`` is the final candidate count requested for plan ``j``;
        diverse plans return the oversampled MMR pool (the caller finishes
        with :func:`finalize_candidates`) — UNLESS the backend fuses MMR
        on device (``self.device_mmr``; see :class:`_DeviceMMRMixin`), in
        which case diverse plans come back as the final k, MMR-ordered,
        with relevance scores.  ``fused_mmr`` overrides per call: None
        defers to ``self.device_mmr``, False forces the host-pool
        contract (the equivalence suites and benches use it to compare
        both paths on one backend); the host-path backends ignore it.

        ``mask`` is an optional bool array, True = live — either (N,)
        shared by every plan, or an (N, B) panel giving each plan its OWN
        eligible rows (the heterogeneous-filter batch path).  Masked rows
        score -inf BEFORE selection (tombstoned segment rows never reach a
        candidate list with a real score — device backends apply the mask
        on device).  When fewer than ``w`` rows are eligible, the -inf
        entries trail the result; :func:`score_select_segments` filters
        them.
        """
        panel = self.score_panel(matrix, days_ago, plans)
        n = panel.shape[0]
        out: List[Candidates] = []
        for j, (plan, k) in enumerate(zip(plans, ks)):
            w = selection_width(plan, k, n)
            if w == 0:
                out.append(_empty_candidates())
                continue
            col = panel[:, j]
            if score_bias is not None:
                b = score_bias[:, j] if score_bias.ndim == 2 else score_bias
                col = col + b  # new array: the panel is never mutated
            if mask is not None:
                m = mask[:, j] if mask.ndim == 2 else mask
                col = np.where(m, col, -np.inf)
            idx = top_idx(col, w)
            out.append((idx, col[idx].astype(np.float32, copy=False)))
        return out

    def __repr__(self) -> str:  # pragma: no cover
        return f"<ExecutionBackend {self.name}>"


class ReferenceNumpyBackend(ExecutionBackend):
    """Paper-faithful: one matvec per direction, exactly Table 1."""

    name = "reference-numpy"

    def score(self, matrix, days_ago, plan):
        return np.asarray(M.modulate_scores(matrix, days_ago, plan))

    def score_panel(self, matrix, days_ago, plans):
        cols = [self.score(matrix, days_ago, p) for p in plans]
        return np.stack(cols, axis=1)


class FusedNumpyBackend(ExecutionBackend):
    """Folded two-matvec formulation: the corpus matrix streams once.

    scores[:, j] = decay_j * (M @ q_pre[:, j]) + M @ q_sup[:, j]
    with per-request decay half-lives applied column-wise.
    """

    name = "fused-numpy"

    def score(self, matrix, days_ago, plan):
        return np.asarray(M.fused_modulate_scores(matrix, days_ago, plan))

    def score_panel(self, matrix, days_ago, plans):
        for p in plans:
            _require_days(p, days_ago)
        q_pre, q_sup = M.fold_plans(plans)
        out = matrix @ q_pre                            # ONE pass (N, B)
        # decay touches only its own columns (strided but rare); the sup
        # add stays one contiguous vectorized op over the whole panel —
        # a per-column `out[:, j] = col + sup[:, j]` loop costs ~40% of
        # the matmuls again in strided traffic at panel widths
        for j, plan in enumerate(plans):
            if plan.decay is not None:
                out[:, j] *= _decay_column(days_ago, plan.decay.half_life_days)
        out += matrix @ q_sup
        return out


class JitJaxBackend(_DeviceMMRMixin, _DeviceMatrixMixin, ExecutionBackend):
    """The fused formulation jitted through XLA (CPU/GPU/TPU portable).

    Per-request decay folds into a (N, B) factor panel; half_life=inf makes
    the factor exactly 1.0 for no-decay columns, so one jitted graph serves
    every plan mix without recompiling on plan structure.

    :meth:`score_select` fuses ``jax.lax.top_k`` — and, for diverse plans,
    the :func:`_device_mmr_trace` MMR tail — into the jitted graph, so only
    the final (B, k) candidate block leaves the device: never the (N, B)
    score panel, never the MMR oversample pool.  Graphs specialize per
    :class:`PlanStructure` through the :class:`PlanCache` (no-decay plans
    skip the decay factor, suppress-free plans skip the second matmul,
    MMR-free batches skip the selection loop entirely).
    """

    name = "jit-jax"

    def __init__(self) -> None:
        self._fn = None
        self.plan_cache = PlanCache(self._build_select)

    def _build(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def fused(matrix, q_pre, q_sup, days, half_lives):
            decay = 1.0 / (1.0 + days[:, None] / half_lives[None, :])
            return (decay * jnp.dot(matrix, q_pre, precision=_HIGHEST)
                    + jnp.dot(matrix, q_sup, precision=_HIGHEST))

        return fused

    def _build_select(self, structure: PlanStructure):
        import jax
        import jax.numpy as jnp

        cache = self.plan_cache

        def fused_select(matrix, q_pre, q_sup, days, half_lives, mask,
                         lams, pool_w, bias):
            cache.jax_traces += 1  # python body runs only while tracing
            # the scopes name the stages in the ops' metadata (what a
            # device trace groups by); the computation is unchanged
            with jax.named_scope("score"):
                scores = jnp.dot(matrix, q_pre, precision=_HIGHEST)
                if structure.has_decay:
                    scores = scores * (
                        1.0 / (1.0 + days[:, None] / half_lives[None, :])
                    )
                if structure.suppress_bucket:
                    scores = scores + jnp.dot(matrix, q_sup,
                                              precision=_HIGHEST)
                if structure.bias:
                    # hybrid lexical leg: additive fusion before mask/top-k
                    scores = scores + bias
                # one mask covers pow2 row padding AND segment tombstones;
                # a panel structure carries one mask column PER PLAN
                scores = jnp.where(mask if structure.panel
                                   else mask[:, None], scores, -jnp.inf)
            with jax.named_scope("select"):
                v, i = jax.lax.top_k(scores.T, structure.width)  # (B, w)
            if structure.mmr_k:
                # fused diverse tail: MMR over the (B, width) pool without
                # leaving the graph (non-diverse columns ride along with
                # lam=1.0, which IS top-k order); positions past each
                # plan's true pool re-mask to -inf so downstream filters
                # treat them exactly like unselected top-k padding
                with jax.named_scope("mmr"):
                    sel = _device_mmr_trace(matrix[i], v, lams, pool_w,
                                            structure.mmr_k)
                    i = jnp.take_along_axis(i, sel, axis=1)
                    v = jnp.take_along_axis(v, sel, axis=1)
                    keep = (jnp.arange(structure.mmr_k)[None, :]
                            < pool_w[:, None])
                    v = jnp.where(keep, v, -jnp.inf)
            return i, v

        return jax.jit(fused_select)

    def score_panel(self, matrix, days_ago, plans):
        for p in plans:
            _require_days(p, days_ago)
        if self._fn is None:
            self._fn = self._build()
        q_pre, q_sup = M.fold_plans(plans)
        n = matrix.shape[0]
        return np.asarray(
            self._fn(self._device_matrix(matrix), q_pre, q_sup,
                     _days_f32(days_ago, n), _half_lives(plans))
        )

    def score_select(self, matrix, days_ago, plans, ks, *, mask=None,
                     fused_mmr=None, score_bias=None, cohort=False,
                     counters=None):
        for p in plans:
            _require_days(p, days_ago)
        n = matrix.shape[0]
        if n == 0:
            return [_empty_candidates() for _ in plans]
        widths = [selection_width(p, k, n) for p, k in zip(plans, ks)]
        use_mmr = self._use_mmr(plans, fused_mmr)
        panel2d = mask is not None and mask.ndim == 2
        structure = PlanStructure.of(plans, widths, n, ks=ks,
                                     device_mmr=use_mmr, panel=panel2d,
                                     bias=score_bias is not None,
                                     cohort=cohort)
        fn = self.plan_cache.get(structure)
        pad = structure.n_rows - n
        q_pre, q_sup, half_lives, lams = _panel_inputs(plans, structure,
                                                       use_mmr)
        days = np.pad(_days_f32(days_ago, n), (0, pad))
        if panel2d:
            live = np.zeros((structure.n_rows, structure.batch), dtype=bool)
            live[:n, :len(plans)] = mask
        else:
            live = np.zeros(structure.n_rows, dtype=bool)
            live[:n] = True if mask is None else mask
        pool_w = _pool_widths(widths, mask, n, structure.batch)
        # no-bias structures take a dummy (1, 1) input: the traced body
        # never touches it, so the arg shape stays stable per structure
        bias = (_expand_bias(score_bias, structure.n_rows, structure.batch,
                             len(plans))
                if structure.bias else np.zeros((1, 1), np.float32))
        _count_uploads(counters, q_pre, q_sup, days, half_lives, live, lams,
                       pool_w, bias)
        idx, vals = fn(self._device_matrix(matrix, pad, counters), q_pre,
                       q_sup, days, half_lives, live, lams, pool_w, bias)
        # with the fused MMR tail the device returns final-k blocks for
        # every plan (plain plans ride the lam=1.0 identity)
        out_w = ([min(max(k, 0), w) for k, w in zip(ks, widths)]
                 if use_mmr else widths)
        return _slice_candidates(idx, vals, out_w)


class PallasBackend(_DeviceMMRMixin, _DeviceMatrixMixin, ExecutionBackend):
    """The fused TPU kernels (``repro.kernels.pem_score`` + ``mmr``).

    The scoring kernel takes one decay column per call, so requests group
    by half-life and each group scores in one kernel launch;
    :meth:`score_select` keeps the score panel device-resident and selects
    with ``jax.lax.top_k`` on it, then chains the ``kernels/mmr``
    selection kernel for diverse plans — no host hop anywhere in the
    chain, only final candidates come back.
    """

    name = "pallas"
    #: Pallas interpret mode — the one switch for every kernel this backend
    #: launches.  The CPU test suite turns it on (tests/conftest.py); the
    #: served path leaves it off, so on a TPU the kernels always compile and
    #: any other platform fails loudly (``repro.kernels.check_interpret``).
    interpret: bool = False

    def _grouped_panel(self, matrix, days_ago, plans, counters=None):
        """Device-resident (N, B) score panel, columns in plan order."""
        import jax.numpy as jnp

        from repro.kernels.pem_score.ops import pem_score

        q_pre, q_sup = M.fold_plans(plans)
        mat = self._device_matrix(matrix, counters=counters)

        groups: Dict[Optional[float], List[int]] = {}
        for j, plan in enumerate(plans):
            hl = plan.decay.half_life_days if plan.decay is not None else None
            groups.setdefault(hl, []).append(j)

        parts = []
        order: List[int] = []
        for hl, cols in groups.items():
            decay = None
            qp, qs = q_pre[:, cols], q_sup[:, cols]
            if hl is not None:
                decay = np.asarray(_decay_column(days_ago, hl), np.float32)
            _count_uploads(counters, qp, qs, decay)
            parts.append(pem_score(
                mat,
                jnp.asarray(qp),
                jnp.asarray(qs),
                None if decay is None else jnp.asarray(decay),
                interpret=self.interpret,
            ))
            order.extend(cols)
        panel = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        if order != list(range(len(plans))):
            panel = panel[:, np.argsort(np.asarray(order))]
        return panel

    def score_panel(self, matrix, days_ago, plans):
        for p in plans:
            _require_days(p, days_ago)
        return np.asarray(self._grouped_panel(matrix, days_ago, plans))

    def score_select(self, matrix, days_ago, plans, ks, *, mask=None,
                     fused_mmr=None, score_bias=None, cohort=False,
                     counters=None):
        # the kernels take exact shapes (no executable cache keyed on
        # batch), so the cohort flag has nothing to bucket here
        import jax
        import jax.numpy as jnp

        for p in plans:
            _require_days(p, days_ago)
        n = matrix.shape[0]
        if n == 0:
            return [_empty_candidates() for _ in plans]
        widths = [selection_width(p, k, n) for p, k in zip(plans, ks)]
        # same pow2 width bucketing as the PlanCache key, one formula
        # (clamped to the real row count: the kernels take exact shapes,
        # there is no compiled-executable cache to bucket rows for)
        w_stat = min(PlanStructure.of(plans, widths, n).width, n)
        # the same three stage scopes as the jitted graphs
        with jax.named_scope("score"):
            panel = self._grouped_panel(matrix, days_ago, plans, counters)
            if score_bias is not None:
                # hybrid lexical leg: additive fusion on the device-
                # resident panel, before mask/top-k (as the jitted graphs)
                b = np.asarray(score_bias, np.float32)
                _count_uploads(counters, b)
                b = jnp.asarray(b)
                panel = panel + (b if b.ndim == 2 else b[:, None])
            if mask is not None:
                # tombstones (or each plan's candidate-panel column) drop
                # out on device, before selection
                _count_uploads(counters, np.asarray(mask))
                m = jnp.asarray(mask)
                panel = jnp.where(m if m.ndim == 2 else m[:, None],
                                  panel, -jnp.inf)
        with jax.named_scope("select"):
            v, i = jax.lax.top_k(panel.T, w_stat)
        if not self._use_mmr(plans, fused_mmr):
            return _slice_candidates(i, v, widths)
        # fused diverse tail: the kernels/mmr pallas kernel selects over
        # each diverse plan's device-resident pool — only the final k
        # (with relevance scores) comes back, never the pool
        from repro.kernels.mmr.ops import mmr_select

        pool_w = _pool_widths(widths, mask, n, len(plans))
        mat = self._any_device_matrix(matrix)
        out = _slice_candidates(i, v, widths)
        for j, (p, k) in enumerate(zip(plans, ks)):
            if p.diverse is None:
                continue
            pw = int(pool_w[j])
            kf = min(max(k, 0), pw)
            if kf == 0:
                out[j] = _empty_candidates()
                continue
            pool_i = i[j, :pw]
            with jax.named_scope("mmr"):
                sel, _ = mmr_select(mat[pool_i][None], v[j, :pw][None], kf,
                                    float(p.diverse.lam),
                                    interpret=self.interpret)
            out[j] = (np.asarray(jnp.take(pool_i, sel[0])).astype(np.int64),
                      np.asarray(jnp.take(v[j, :pw], sel[0])))
        return out

    def mmr_pool_segments(self, segments, gidx, vals, k, lam):
        """Merged-pool MMR through the ``kernels/mmr`` pallas kernel
        (pool pow2-bucketed with NEG-masked padding so the kernel compiles
        a bounded set of shapes)."""
        import jax.numpy as jnp

        from repro.kernels.mmr.kernel import NEG
        from repro.kernels.mmr.ops import mmr_select

        pool = int(gidx.size)
        k = max(0, min(int(k), pool))
        if k == 0:
            return np.empty(0, np.int64)
        emb = self._gather_pool_device(segments, np.asarray(gidx, np.int64))
        bucket = max(_pow2_bucket(pool), 1)
        if bucket != pool:
            emb = jnp.pad(emb, ((0, bucket - pool), (0, 0)))
        rel = np.full(bucket, NEG, np.float32)
        rel[:pool] = vals
        sel, _ = mmr_select(emb[None], jnp.asarray(rel)[None], k,
                            float(lam), interpret=self.interpret)
        return np.asarray(sel)[0].astype(np.int64)

    def mmr_pool_segments_batch(self, segments, pools, ks, lams):
        """The ``kernels/mmr`` pallas kernel takes a scalar lambda, so a
        heterogeneous-lambda cohort falls back to one kernel launch per
        plan (still zero host pool transfers)."""
        return [self.mmr_pool_segments(segments, g, v, k, lam)
                for (g, v), k, lam in zip(pools, ks, lams)]


class ShardedBackend(_DeviceMMRMixin, _DeviceMatrixMixin, ExecutionBackend):
    """shard_map row-sharded scoring over every locally visible device.

    The corpus rows split across a 1-D device mesh; each shard computes its
    slice of the fused score panel.  :meth:`score_panel` reassembles the
    panel on the host; :meth:`score_select` instead folds the
    ``repro.dist.pem_sharded`` two-stage selection into the graph — each
    shard takes a LOCAL top-k and only the (shards * k, B) candidate union
    crosses the interconnect before the merge, never the (N, B) panel.
    The fused MMR tail for diverse plans runs AFTER the shard_map, on the
    replicated merged union, inside the same jitted graph.
    """

    name = "sharded"

    def __init__(self) -> None:
        self._fn = None
        self._shards_mesh = None
        self.plan_cache = PlanCache(self._build_select)

    def _mesh(self):
        """The 1-D ``shards`` mesh over every local device (built once)."""
        if self._shards_mesh is None:
            import jax
            from jax.sharding import AxisType

            self._shards_mesh = jax.make_mesh(
                (len(jax.devices()),), ("shards",),
                axis_types=(AxisType.Auto,))
        return self._shards_mesh

    def _place(self, mat: np.ndarray, pad: int):
        """Place the corpus row-sharded over the mesh, so each device
        holds its own 1/shards of the rows and ``shard_map`` never
        reshards it per call.  Each device receives a view of its own row
        slice: no host copy of the whole matrix is made.  Only the slice
        that reaches past the true rows is copied, to end in the zero
        rows of ``pad`` and of the shard multiple (past the true row
        count, where nothing indexes).  The slices go one at a time, each
        transfer finished before the next starts: the runtime stages a
        host copy of what it is sent, and all shards sent at once would
        stage the whole matrix again."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        n, d = mat.shape
        mesh = self._mesh()
        rows = n + pad
        rows += (-rows) % mesh.size
        sharding = NamedSharding(mesh, P("shards", None))

        def shard(index):
            lo, hi, _ = index[0].indices(rows)
            if hi <= n:
                return mat[lo:hi]
            out = np.zeros((hi - lo, d), mat.dtype)
            out[:max(n - lo, 0)] = mat[lo:n]
            return out

        parts = []
        for device, index in sharding.addressable_devices_indices_map((rows, d)).items():
            parts.append(jax.device_put(shard(index), device).block_until_ready())
        return jax.make_array_from_single_device_arrays((rows, d), sharding, parts)

    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def local(matrix, q_pre, q_sup, days, half_lives):
            decay = 1.0 / (1.0 + days[:, None] / half_lives[None, :])
            return (decay * jnp.dot(matrix, q_pre, precision=_HIGHEST)
                    + jnp.dot(matrix, q_sup, precision=_HIGHEST))

        fn = jax.shard_map(
            local,
            mesh=self._mesh(),
            in_specs=(P("shards", None), P(None, None), P(None, None),
                      P("shards"), P(None)),
            out_specs=P("shards", None),
            check_vma=False,
        )
        return jax.jit(fn)

    def _build_select(self, structure: PlanStructure):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.dist.pem_sharded import (union_merge_topk,
                                            union_merge_topk_payload)

        mesh = self._mesh()
        cache = self.plan_cache

        def local(matrix, q_pre, q_sup, days, half_lives, mask, bias):
            cache.jax_traces += 1  # python body runs only while tracing
            n_local = matrix.shape[0]
            shard = jax.lax.axis_index("shards")
            with jax.named_scope("score"):
                scores = jnp.dot(matrix, q_pre, precision=_HIGHEST)
                if structure.has_decay:
                    scores = scores * (
                        1.0 / (1.0 + days[:, None] / half_lives[None, :])
                    )
                if structure.suppress_bucket:
                    scores = scores + jnp.dot(matrix, q_sup,
                                              precision=_HIGHEST)
                if structure.bias:
                    # hybrid lexical leg, sharded row-wise like the mask
                    scores = scores + bias
                # one mask covers row-grid padding AND segment tombstones,
                # so neither can ever enter the union with a real score; a
                # panel structure shards one mask column PER PLAN instead
                scores = jnp.where(mask if structure.panel
                                   else mask[:, None], scores, -jnp.inf)
            with jax.named_scope("select"):
                k_local = min(structure.width, n_local)
                v, i = jax.lax.top_k(scores.T, k_local)  # (B, k_local)
                gi = i + shard * n_local                  # global row ids
                if structure.mmr_k:
                    # shard-local MMR prefix: each shard gathers its OWN
                    # candidates' pool embeddings (an O(n_local) gather)
                    # and the payload merge ships them with the union —
                    # the MMR tail then never touches the replicated rows
                    pe = matrix[i]                        # (B, k_l, d)
            # the cross-chip union merge names its own ``merge`` scope
            if structure.mmr_k:
                return union_merge_topk_payload(v, gi, pe, ("shards",),
                                                structure.width)
            return union_merge_topk(v, gi, ("shards",), structure.width)

        out_specs = ((P(None, None), P(None, None), P(None, None, None))
                     if structure.mmr_k else (P(None, None), P(None, None)))
        inner = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P("shards", None), P(None, None), P(None, None),
                      P("shards"), P(None),
                      P("shards", None) if structure.panel else P("shards"),
                      P("shards", None) if structure.bias else P(None, None)),
            out_specs=out_specs,
            check_vma=False,
        )

        def fused_select(matrix, q_pre, q_sup, days, half_lives, mask,
                         lams, pool_w, bias):
            if structure.mmr_k:
                # fused diverse tail over the payload-merged pool: the
                # merged (B, width, d) embeddings arrived with the union
                # (shard-local gathers, O(shards*width*d) collective —
                # independent of corpus size), bit-identical to the old
                # replicated ``matrix[i]`` gather because the payload
                # rode the exact top-k permutation the indices did
                i, v, pe = inner(matrix, q_pre, q_sup, days, half_lives,
                                 mask, bias)
                with jax.named_scope("mmr"):
                    sel = _device_mmr_trace(pe, v, lams, pool_w,
                                            structure.mmr_k)
                    i = jnp.take_along_axis(i, sel, axis=1)
                    v = jnp.take_along_axis(v, sel, axis=1)
                    keep = (jnp.arange(structure.mmr_k)[None, :]
                            < pool_w[:, None])
                    v = jnp.where(keep, v, -jnp.inf)
            else:
                i, v = inner(matrix, q_pre, q_sup, days, half_lives, mask,
                             bias)
            return i, v

        return jax.jit(fused_select)

    def score_panel(self, matrix, days_ago, plans):
        for p in plans:
            _require_days(p, days_ago)
        if self._fn is None:
            self._fn = self._build()
        q_pre, q_sup = M.fold_plans(plans)
        n = matrix.shape[0]
        days = _days_f32(days_ago, n)
        # pad the row grid to the shard count, slice the panel back
        pad = (-n) % self._mesh().size
        mat = self._device_matrix(matrix, pad)
        if pad:
            days = np.pad(days, (0, pad))
        out = np.asarray(self._fn(mat, q_pre, q_sup, days, _half_lives(plans)))
        return out[:n]

    def score_select(self, matrix, days_ago, plans, ks, *, mask=None,
                     fused_mmr=None, score_bias=None, cohort=False,
                     counters=None):
        for p in plans:
            _require_days(p, days_ago)
        n = matrix.shape[0]
        if n == 0:
            return [_empty_candidates() for _ in plans]
        n_shards = self._mesh().size
        widths = [selection_width(p, k, n) for p, k in zip(plans, ks)]
        use_mmr = self._use_mmr(plans, fused_mmr)
        panel2d = mask is not None and mask.ndim == 2
        structure = PlanStructure.of(plans, widths, n, ks=ks,
                                     device_mmr=use_mmr, panel=panel2d,
                                     bias=score_bias is not None,
                                     cohort=cohort)
        fn = self.plan_cache.get(structure)
        # row grid (the PlanCache key), then up to a shard multiple —
        # derived from the grid alone, so one trace per grid step
        padded = structure.n_rows + ((-structure.n_rows) % n_shards)
        pad = padded - n
        q_pre, q_sup, half_lives, lams = _panel_inputs(plans, structure,
                                                       use_mmr)
        days = np.pad(_days_f32(days_ago, n), (0, pad))
        if panel2d:
            live = np.zeros((padded, structure.batch), dtype=bool)
            live[:n, :len(plans)] = mask
        else:
            live = np.zeros(padded, dtype=bool)
            live[:n] = True if mask is None else mask
        pool_w = _pool_widths(widths, mask, n, structure.batch)
        mat = self._device_matrix(matrix, pad, counters)
        # bias shards row-wise with the corpus grid; no-bias structures
        # take a replicated dummy the traced body never touches
        bias = (_expand_bias(score_bias, padded, structure.batch,
                             len(plans))
                if structure.bias else np.zeros((1, 1), np.float32))
        _count_uploads(counters, q_pre, q_sup, days, half_lives, live, lams,
                       pool_w, bias)
        idx, vals = fn(mat, q_pre, q_sup, days, half_lives, live, lams,
                       pool_w, bias)
        out_w = ([min(max(k, 0), w) for k, w in zip(ks, widths)]
                 if use_mmr else widths)
        return _slice_candidates(idx, vals, out_w)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ExecutionBackend] = {}
_ALIASES = {
    # the seed's public engine strings keep working
    "reference": "reference-numpy",
    "fused": "fused-numpy",
    "jax": "jit-jax",
}


def register_backend(backend: ExecutionBackend) -> ExecutionBackend:
    _REGISTRY[backend.name] = backend
    return backend


register_backend(ReferenceNumpyBackend())
register_backend(FusedNumpyBackend())
register_backend(JitJaxBackend())
register_backend(PallasBackend())
register_backend(ShardedBackend())


def list_backends() -> List[str]:
    """Canonical names of every registered backend."""
    return sorted(_REGISTRY)


def get_backend(engine: Union[str, ExecutionBackend]) -> ExecutionBackend:
    """Resolve an engine name (or pass an ExecutionBackend through)."""
    if isinstance(engine, ExecutionBackend):
        return engine
    name = _ALIASES.get(engine, engine)
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {engine!r}; known: {list_backends()} "
            f"(aliases: {sorted(_ALIASES)})"
        ) from None


# ---------------------------------------------------------------------------
# Shared selection (identical ranking on batched and direct paths)
# ---------------------------------------------------------------------------


def top_idx(scores: np.ndarray, k: int) -> np.ndarray:
    """Indices of the top-k scores, sorted descending (argpartition+sort).

    Ties break toward the SMALLEST index — the same rule as
    ``jax.lax.top_k`` and the stable merges built on top of this, so the
    numpy and device backends agree bit-for-bit on tied scores and a
    cross-shard merge keyed on global row order reproduces the
    monolithic ranking exactly.  ``argpartition`` alone picks an
    arbitrary member set when ties straddle the k boundary, so the
    boundary value's members are re-resolved by index explicitly (two
    extra O(n) scans, negligible next to the scoring matmul).
    """
    if k >= scores.shape[0]:
        return np.argsort(-scores, kind="stable")
    part = np.argpartition(-scores, k)[:k]
    vstar = scores[part].min()  # the k-th largest value
    strictly = np.flatnonzero(scores > vstar)
    ties = np.flatnonzero(scores == vstar)
    members = np.concatenate([strictly, ties[: k - strictly.size]])
    return members[np.argsort(-scores[members], kind="stable")]


def selection_width(plan: M.ModulationPlan, k: int, n: int) -> int:
    """Candidates a backend must return for (plan, k) over n rows.

    Plain plans need exactly k; diverse plans need the MMR oversample pool
    ``oversample * max(k, plan.pool)`` so a small-k request (batched path)
    and a pool-sized request (direct path) draw from the same pool — MMR's
    greedy selection is prefix-consistent, so their rankings agree.
    """
    k = max(0, min(k, n))
    if k == 0:
        return 0
    if plan.diverse is not None:
        return min(plan.diverse.oversample * max(k, plan.pool), n)
    return k


def finalize_candidates(
    matrix: np.ndarray,
    idx: np.ndarray,
    scores: np.ndarray,
    k: int,
    plan: M.ModulationPlan,
) -> Candidates:
    """Host finishing stage over backend-returned candidates.

    Truncates a plain top-k pool to k, or runs MMR over the oversampled
    pool for diverse plans.  Produces exactly what
    :func:`select_candidates` yields on the full score array (same
    indices, same order), but only ever touches (pool,)-sized inputs.
    """
    k = max(0, min(k, idx.shape[0]))
    if k == 0:
        return idx[:0], scores[:0]
    if plan.diverse is not None:
        sel = mmr_host(matrix[idx], scores, k, plan.diverse.lam)
        return idx[sel], scores[sel]
    return idx[:k], scores[:k]


def score_select_segments(
    backend: Union[str, "ExecutionBackend"],
    segments: Sequence,
    plans: Sequence[M.ModulationPlan],
    ks: Sequence[int],
    *,
    now: Optional[float] = None,
    candidate_masks: Optional[Sequence[Optional[np.ndarray]]] = None,
    device_mmr: Optional[bool] = None,
    counters: Optional[FusedCounters] = None,
    score_bias: Optional[Sequence[Optional[np.ndarray]]] = None,
    cohort: bool = False,
) -> List[Candidates]:
    """Fused score->select over a SEGMENTED corpus (repro.core.segments).

    This is the DEVICE PASS of the segmented pipeline — the stage that
    touches device memory (per-segment scoring + on-device selection).
    Its host counterpart is :func:`finalize_segment_candidates` (gather +
    truncate/MMR + id resolution), which needs only the immutable segment
    snapshot — never the store lock or the device — so a serving core can
    overlap the host tail of batch *i* with the device pass of batch
    *i+1* (the async engine in :mod:`repro.serve.engine` does exactly
    that).

    Each segment scores independently through ``backend.score_select``
    (its tombstones masked to -inf on device before selection), then the
    per-segment top-k candidates merge on the host — the same two-stage
    union-merge shape ``dist/pem_sharded.union_merge_topk`` applies across
    device shards, applied across segments: every segment's local top-w
    provably contains its share of the global top-w, so the merge is
    exact.  Returns per-plan ``(global_rows, scores)`` where global rows
    offset into the concatenation of ALL segment rows (tombstoned rows
    included, so offsets are stable under deletes); resolve them with
    ``segments.gather_rows`` / ``segments.gather_ids``.

    Tie-breaking matches the monolithic path bit-for-bit: within a
    segment both ``top_idx`` and ``jax.lax.top_k`` prefer the smallest
    row, and the merge's stable sort keeps segment-major order, which IS
    global row order.

    ``ks[j]`` is the final candidate count for plan ``j``; diverse plans
    come back as the oversampled MMR pool (callers finish with
    :func:`finalize_candidates` over gathered candidate embeddings),
    exactly like the monolithic ``score_select`` — UNLESS the backend
    fuses MMR on device (``backend.device_mmr`` and ``device_mmr`` is not
    forced False), in which case EVERY diverse plan is device-finalized:
    the fast path fuses MMR into the scoring graph, and the per-segment
    path runs :meth:`_DeviceMMRMixin.mmr_pool_segments` over the merged
    pool (gathered from the warm resident segment matrices, never the
    host).  Callers can then finish with ``mmr_done=backend.device_mmr``.

    ``candidate_masks`` is the Phase-1 filtered-retrieval hook: per-segment
    bool masks (``SegmentedCorpusStore.candidate_masks``; None = segment
    holds no candidate, skipped entirely) — or per-segment (n, B) PANELS
    (``SegmentedCorpusStore.candidate_mask_panel``) giving each plan its
    own candidate column for heterogeneous-filter batches.  Each mask
    composes with the segment's tombstones — candidates ∧ live score,
    everything else hits -inf ON DEVICE before selection — so a
    pre-filtered query scores the same warm device-resident segment
    matrices as an unfiltered one: zero per-query gather, zero per-query
    upload, plan-cache row buckets unchanged.  Selection widths shrink to
    each plan's eligible-row count, and the union merge is bit-identical
    to host-gathering the candidate rows (in global-row order) and
    scoring them monolithically.

    ``score_bias`` is the hybrid-fusion hook: per-segment additive score
    arrays aligned with ``segments`` (None = zero bias; (n,) shared or
    (n, B) per-plan — ``SegmentedCorpusStore.score_bias_arrays`` /
    :func:`fusion_bias_arrays` build them), added on device before
    masking and selection.  A candidate-mask skip stays a skip: the
    Phase-1 filter is hard, bias only re-ranks eligible rows.
    """
    from repro.core.segments import segment_offsets

    backend = get_backend(backend)
    if candidate_masks is not None and len(candidate_masks) != len(segments):
        raise ValueError("candidate_masks misaligned with segments")
    if score_bias is not None and len(score_bias) != len(segments):
        raise ValueError("score_bias misaligned with segments")
    nplans = len(plans)
    # per-segment eligible mask: candidates ∧ live (None = every row);
    # per-PLAN eligible counts — a (n, B) panel gives every plan its own
    # column, so counts (and selection widths) differ per plan
    scored: List[Tuple[int, object, Optional[np.ndarray], np.ndarray]] = []
    elig = np.zeros(nplans, dtype=np.int64)
    for i, s in enumerate(segments):
        if not s.n_rows or not s.live_count:
            continue
        if candidate_masks is not None:
            cm = candidate_masks[i]
            if cm is None:
                continue
            if cm.ndim == 2:
                m = (cm & s.live_mask[:, None]) if s.n_dead else cm
                c = np.count_nonzero(m, axis=0).astype(np.int64)
                if not c.any():
                    continue
                if int(c.min()) == s.n_rows:
                    m = None  # every plan sees every row: unmasked shape
            else:
                m = (cm & s.live_mask) if s.n_dead else cm
                c1 = int(np.count_nonzero(m))
                if c1 == 0:
                    continue
                if c1 == s.n_rows:
                    m = None  # every row eligible: the unmasked fast shape
                c = np.full(nplans, c1, dtype=np.int64)
        else:
            m = s.live_mask if s.n_dead else None
            c = np.full(nplans, s.live_count, dtype=np.int64)
        scored.append((i, s, m, c))
        elig += c
    if not scored or not nplans:
        return [_empty_candidates() for _ in plans]
    if now is None:
        now = time.time()
    offsets = segment_offsets(segments)
    use_mmr = (backend.device_mmr and device_mmr is not False
               and any(p.diverse is not None for p in plans))

    # fast path: one segment with every row eligible IS the monolithic
    # corpus — same call, same candidates, zero segmentation overhead
    # (device-MMR backends finish diverse plans inside the fused graph)
    if len(scored) == 1 and scored[0][2] is None:
        i, seg, _, c = scored[0]
        n_el = int(c[0])
        out = backend.score_select(
            seg.matrix, seg.days_ago(now), plans,
            [min(k, n_el) for k in ks], fused_mmr=device_mmr,
            score_bias=None if score_bias is None else score_bias[i],
            cohort=cohort, counters=counters)
        if use_mmr and counters is not None:
            counters.device_mmr += sum(
                1 for p, k in zip(plans, ks)
                if p.diverse is not None and min(k, n_el) > 0)
        if offsets[i]:
            out = [(idx + offsets[i], vals) for idx, vals in out]
        return out

    # per-plan GLOBAL selection widths over each plan's ELIGIBLE rows
    # (diverse oversampling applies once, at corpus level; per-segment
    # requests are plain top-w)
    ks_eff = [min(k, int(e)) for k, e in zip(ks, elig)]
    widths = [selection_width(p, ke, int(e))
              for p, ke, e in zip(plans, ks_eff, elig)]
    seg_plans = [dataclasses.replace(p, diverse=None)
                 if p.diverse is not None else p for p in plans]

    parts: List[List[Candidates]] = []
    for i, seg, m, _ in scored:
        sel = backend.score_select(
            seg.matrix, seg.days_ago(now), seg_plans, widths, mask=m,
            score_bias=None if score_bias is None else score_bias[i],
            cohort=cohort, counters=counters)
        parts.append([(idx + offsets[i], vals) for idx, vals in sel])

    merged: List[Candidates] = []
    for j, w in enumerate(widths):
        if w == 0:
            merged.append(_empty_candidates())
            continue
        cat_i = np.concatenate([p[j][0] for p in parts])
        cat_v = np.concatenate([p[j][1] for p in parts])
        live = ~np.isneginf(cat_v)  # mask/padding leakage ends here
        cat_i, cat_v = cat_i[live], cat_v[live]
        order = np.argsort(-cat_v, kind="stable")[:w]
        merged.append((cat_i[order], cat_v[order]))

    if use_mmr:
        # merged-pool fused diverse tail: the union-merged pool equals
        # the monolithic oversample pool, so device MMR over it (pool
        # embeddings gathered from the warm resident segment matrices)
        # is exact — diverse plans leave here final-k, never as a pool.
        # The whole diverse cohort pads into ONE batched device call
        # (mmr_pool_segments_batch) instead of one sync per plan.
        div = [j for j, p in enumerate(plans)
               if p.diverse is not None and merged[j][0].size]
        if div:
            sels = backend.mmr_pool_segments_batch(
                segments, [merged[j] for j in div],
                [min(ks_eff[j], int(merged[j][0].size)) for j in div],
                [plans[j].diverse.lam for j in div])
            for j, sel in zip(div, sels):
                gidx, gv = merged[j]
                merged[j] = (gidx[sel], gv[sel])
            if counters is not None:
                counters.device_mmr += 1
    return merged


def score_select_cohort(
    backend: Union[str, "ExecutionBackend"],
    segments: Sequence,
    plans: Sequence[M.ModulationPlan],
    ks: Sequence[int],
    *,
    now: Optional[float] = None,
    candidate_masks: Optional[Sequence[Optional[np.ndarray]]] = None,
    device_mmr: Optional[bool] = None,
    counters: Optional[FusedCounters] = None,
    score_bias: Optional[Sequence[Optional[np.ndarray]]] = None,
) -> List[Candidates]:
    """Cohort-panel score->select: one device pass for a MULTI-QUERY batch.

    ``plans`` here is a cohort — one plan per admitted query, folded into
    one fused ``(d, 2·Q)`` query panel so each segment matrix streams
    through device memory once per cohort instead of once per query.
    Execution is :func:`score_select_segments` with ``cohort=True``, which
    pow2-buckets the BATCH axis of the :class:`PlanStructure` cache key on
    device backends: a stream of varying cohort sizes (Q=3, Q=5, Q=7 …)
    compiles one executable per pow2 bucket, padded columns carry zero
    queries and are sliced away.  Rankings are bit-identical to Q serial
    single-plan calls on the same snapshot — cohort mode reorders loops,
    never arithmetic.  The cross-process analogue (one RPC, one corpus
    stream per shard per cohort) is ``dist.procgroup.ProcessGroup``'s
    ``search_plan_batch``.
    """
    return score_select_segments(
        backend, segments, plans, ks, now=now,
        candidate_masks=candidate_masks, device_mmr=device_mmr,
        counters=counters, score_bias=score_bias, cohort=True)


@dataclasses.dataclass
class PrefilterRouter:
    """Selectivity-aware router for Phase-1 filtered retrieval.

    Two ways to score a pre-filtered sub-corpus, with opposite cost
    shapes (Bruch, *Foundations of Vector Retrieval* §filtered search):

    * **masked-device** — score the warm device-resident segment matrices
      with non-candidates masked to -inf before selection.  Cost is
      O(corpus) but every byte is already on device: zero gather, zero
      upload, plan-cache hits preserved.  Wins when the filter is weak
      (candidates are a large fraction of the corpus).
    * **gather-host** — resolve the candidate rows through the id index
      (O(candidates)), gather them into a scratch matrix and score that.
      Pays a host gather + device upload + (first time) a trace per row
      bucket EVERY query, but touches only candidate rows.  Wins when the
      filter is sharp (a few hundred rows out of a million).

    The router picks per query on REQUESTED selectivity — unique
    candidate count over live rows — against the crossover threshold.
    ``mask_threshold`` seeds it statically (the measured crossover lives
    in ``BENCH_pem.json``'s ``prefilter_backends`` scenario); with
    ``adaptive`` on, the router then LEARNS the crossover from its own
    recorded timing samples: masked cost is bandwidth-bound in live rows
    (≈ ``a·n_live``), gather cost is linear in candidates
    (≈ ``b·n_candidates``), so masked wins once ``a·n_live ≤
    b·n_candidates`` — i.e. at selectivity ≥ ``a/b``.  Until BOTH arms
    have ``min_samples`` recorded passes the static seed stays in force,
    and the learned value is clamped to [0.01, 0.9] so one degenerate
    timing sample can't pin the router to a single arm.  Counters are
    benign int/float bumps (same convention as the store's) surfaced
    through ``RetrievalService.stats()["prefilter"]``.
    """

    mask_threshold: float = 0.2  # static seed: selectivity where masked wins
    adaptive: bool = True        # learn the crossover from timing samples
    min_samples: int = 5         # per-arm passes before the learned value arms
    routed_masked: int = 0       # queries served by the masked-device path
    routed_gather: int = 0       # queries served by the gather-host path
    routed_panel: int = 0        # queries served by a batched (N, B) panel
    mask_build_ms: float = 0.0   # cumulative candidate-mask build time
    masked_ms: float = 0.0       # cumulative masked-arm scoring time
    masked_rows: int = 0         # cumulative live rows swept by masked passes
    masked_samples: int = 0
    gather_ms: float = 0.0       # cumulative gather-arm scoring time
    gather_rows: int = 0         # cumulative candidate rows gathered+scored
    gather_samples: int = 0
    # routed_* count QUERIES: a batched scoring call serving n folded
    # identical filters bumps by n (score_select_prefiltered's weight=),
    # and a panel pass serving a B-request cohort bumps routed_panel by B

    def record_masked(self, ms: float, n_live: int) -> None:
        if ms >= 0.0 and n_live > 0:
            self.masked_ms += ms
            self.masked_rows += n_live
            self.masked_samples += 1

    def record_gather(self, ms: float, n_candidates: int) -> None:
        if ms >= 0.0 and n_candidates > 0:
            self.gather_ms += ms
            self.gather_rows += n_candidates
            self.gather_samples += 1

    def effective_threshold(self) -> float:
        if (not self.adaptive
                or self.masked_samples < self.min_samples
                or self.gather_samples < self.min_samples
                or not self.masked_rows or not self.gather_rows
                or self.gather_ms <= 0.0):
            return self.mask_threshold
        a = self.masked_ms / self.masked_rows    # ms per live row swept
        b = self.gather_ms / self.gather_rows    # ms per candidate gathered
        return min(max(a / b, 0.01), 0.9)

    def use_masked(self, n_candidates: int, n_live: int) -> bool:
        return (n_live > 0
                and n_candidates >= self.effective_threshold() * n_live)

    def use_panel(
        self,
        candidate_counts: Sequence[Optional[int]],
        n_live: int,
    ) -> bool:
        """The batched-panel arm: serve a heterogeneous-filter cohort with
        ONE (N, B) mask-panel pass when at least two of its distinct
        filter groups would each cost a full-corpus device pass anyway —
        an unfiltered group (``None``) or a filter the masked arm would
        take.  One batched matmul then replaces those passes outright.
        Below that, per-group dispatch stays (sharp filters keep the
        cheap O(candidates) gather path)."""
        if len(candidate_counts) < 2:
            return False
        full = sum(1 for c in candidate_counts
                   if c is None or self.use_masked(int(c), n_live))
        return full >= 2

    def stats(self) -> Dict[str, Union[int, float]]:
        return {
            "threshold": self.mask_threshold,
            "threshold_effective": round(self.effective_threshold(), 4),
            "routed_masked": self.routed_masked,
            "routed_gather": self.routed_gather,
            "routed_panel": self.routed_panel,
            "mask_build_ms": round(self.mask_build_ms, 3),
            "masked_samples": self.masked_samples,
            "gather_samples": self.gather_samples,
        }


def score_select_prefiltered(
    backend: Union[str, "ExecutionBackend"],
    store,
    segments: Sequence,
    plans: Sequence[M.ModulationPlan],
    ks: Sequence[int],
    candidate_ids: Sequence[int],
    *,
    now: Optional[float] = None,
    router: Optional[PrefilterRouter] = None,
    weight: int = 1,
    device_mmr: Optional[bool] = None,
    counters: Optional[FusedCounters] = None,
    score_bias: Optional[Sequence[Optional[np.ndarray]]] = None,
) -> List[Candidates]:
    """Device pass for a Phase-1 FILTERED micro-batch (one candidate set
    shared by every plan in the call).  ``weight`` is how many QUERIES
    this call serves (the batched engine folds identical filters into one
    call), so the router's counters stay per-query on every path.

    Routes through ``router`` (masked-device vs gather-host, see
    :class:`PrefilterRouter`) and returns per-plan ``(global_rows,
    scores)`` — the same contract as :func:`score_select_segments`, so
    :func:`finalize_segment_candidates` finishes both filtered and
    unfiltered batches identically.  Callers needing a consistent pass
    hold ``store.lock`` across snapshot + this call, exactly like the
    unfiltered driver.

    Non-strict on both routes: candidate ids deleted between the Phase-1
    SQL and this pass (or never known) are silently dropped —
    ``candidate_masks`` never sets their bit, ``locate_rows`` skips them.
    Duplicates collapse (``np.unique``), and ties break by global row on
    both routes, so the two are bit-identical.
    """
    from repro.core.segments import gather_days, gather_rows

    backend = get_backend(backend)
    # avoid python-int boxing for array inputs (the engine already hands
    # over the canonical unique-sorted array from Request admission; the
    # sortedness check below then skips the redundant O(c log c) sort)
    cand = (candidate_ids if isinstance(candidate_ids, np.ndarray)
            else np.asarray(list(candidate_ids), dtype=np.int64))
    cand = cand.astype(np.int64, copy=False).ravel()
    if cand.size > 1 and not np.all(cand[1:] > cand[:-1]):
        cand = np.unique(cand)
    n_live = sum(s.live_count for s in segments)
    if cand.size == 0 or n_live == 0:
        return [_empty_candidates() for _ in plans]
    if router is None:
        router = PrefilterRouter()
    if now is None:
        now = time.time()

    if router.use_masked(int(cand.size), n_live):
        t0 = time.perf_counter()
        masks, matched = store.candidate_masks(cand, segments)
        router.mask_build_ms += (time.perf_counter() - t0) * 1e3
        router.routed_masked += weight
        if matched == 0:
            return [_empty_candidates() for _ in plans]
        t0 = time.perf_counter()
        out = score_select_segments(
            backend, segments, plans, ks, now=now, candidate_masks=masks,
            device_mmr=device_mmr, counters=counters,
            score_bias=score_bias)
        # adaptive crossover: the masked arm's cost scales with the live
        # rows it sweeps, regardless of how few candidates survive
        router.record_masked((time.perf_counter() - t0) * 1e3, n_live)
        return out

    router.routed_gather += weight
    rows = store.locate_rows(cand, segments)
    if rows.size == 0:
        return [_empty_candidates() for _ in plans]
    t0 = time.perf_counter()
    sub = gather_rows(segments, rows)
    days = gather_days(segments, rows, now)
    ks_eff = [min(k, int(rows.size)) for k in ks]
    sub_bias = (None if score_bias is None
                else _gather_bias(score_bias, segments, rows))
    sel = backend.score_select(sub, days, plans, ks_eff,
                               fused_mmr=device_mmr, score_bias=sub_bias,
                               counters=counters)
    # the gather arm pays resolve+gather+upload+score per candidate row
    router.record_gather((time.perf_counter() - t0) * 1e3, int(rows.size))
    if (counters is not None and backend.device_mmr
            and device_mmr is not False):
        counters.device_mmr += sum(
            1 for p, k in zip(plans, ks_eff)
            if p.diverse is not None and k > 0)
    return [(rows[idx], vals) for idx, vals in sel]


def score_select_filter_panel(
    backend: Union[str, "ExecutionBackend"],
    store,
    segments: Sequence,
    plans: Sequence[M.ModulationPlan],
    ks: Sequence[int],
    candidate_sets: Sequence[Optional[Sequence[int]]],
    *,
    now: Optional[float] = None,
    router: Optional[PrefilterRouter] = None,
    counters: Optional[FusedCounters] = None,
    device_mmr: Optional[bool] = None,
    score_bias: Optional[Sequence[Optional[np.ndarray]]] = None,
) -> List[Candidates]:
    """Device pass for a HETEROGENEOUS-filter micro-batch: one plan per
    request, each with its OWN Phase-1 candidate set (None = unfiltered).

    Instead of one scoring pass per distinct filter, builds a per-plan
    (N, B) candidate-mask panel (``SegmentedCorpusStore.
    candidate_mask_panel`` — an unfiltered request rides along as the
    all-live column, so a mixed cohort never splits) and runs ONE batched
    :func:`score_select_segments` pass over the warm segment matrices:
    one matmul + masked selection for the whole cohort.  Returns the same
    per-plan ``(global_rows, scores)`` contract as every other driver,
    and each plan's ranking is bit-identical to dispatching its filter
    through :func:`score_select_prefiltered` on its own.  The batched
    engine consults :meth:`PrefilterRouter.use_panel` first —
    sharp-filter-only cohorts stay on per-group gather dispatch.
    """
    backend = get_backend(backend)
    if now is None:
        now = time.time()
    t0 = time.perf_counter()
    panels, matched = store.candidate_mask_panel(candidate_sets, segments)
    if router is not None:
        router.mask_build_ms += (time.perf_counter() - t0) * 1e3
        router.routed_panel += len(plans)
    if counters is not None:
        counters.panel_batches += 1
    if all(p is None for p in panels):
        return [_empty_candidates() for _ in plans]
    return score_select_segments(
        backend, segments, plans, ks, now=now, candidate_masks=panels,
        device_mmr=device_mmr, counters=counters, score_bias=score_bias)


def _gather_bias(
    bias_arrays: Sequence[Optional[np.ndarray]],
    segments: Sequence,
    rows: np.ndarray,
) -> np.ndarray:
    """Per-segment bias arrays -> bias values at GLOBAL rows (the gather
    route's counterpart of ``gather_rows``: the sub-matrix is scored with
    the matching sub-bias)."""
    from repro.core.segments import segment_offsets

    off = segment_offsets(segments)
    seg_idx = np.searchsorted(off, rows, side="right") - 1
    local = rows - off[seg_idx]
    width = next((a.shape[1] for a in bias_arrays
                  if a is not None and a.ndim == 2), None)
    out = (np.zeros(rows.size, np.float32) if width is None
           else np.zeros((rows.size, width), np.float32))
    for s in np.unique(seg_idx):
        arr = bias_arrays[s]
        if arr is None:
            continue
        take = seg_idx == s
        vals = arr[local[take]]
        if width is not None and vals.ndim == 1:
            vals = np.repeat(vals[:, None], width, axis=1)
        out[take] = vals
    return out


def plan_fusion_bias(
    plan: M.ModulationPlan,
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """One plan's sparse lexical score contribution: ``(chunk_ids,
    (1-w) * minmax(bm25))`` — or None when nothing rides on device
    (no fusion, RRF mode, empty lexical hits, or w == 1.0: the guard
    that keeps ``fuse:weighted,1.0`` bit-identical to the unfused path).
    ``fuse:filter,W`` plans with W < 1 fuse the same way — the hit set
    is already the Phase-1 candidate set, the bias just re-ranks within
    it.
    """
    f = plan.fusion
    if (f is None or f.mode not in ("weighted", "filter")
            or plan.lexical is None
            or plan.lexical.ids.size == 0 or f.weight == 1.0):
        return None
    vals = ((1.0 - f.weight)
            * np.asarray(plan.lexical.scores, np.float32))
    return plan.lexical.ids, vals.astype(np.float32, copy=False)


def fusion_bias_arrays(
    store,
    segments: Sequence,
    plans: Sequence[M.ModulationPlan],
) -> Optional[List[Optional[np.ndarray]]]:
    """Per-segment additive score arrays for a micro-batch's lexical legs
    — the ``score_bias`` input of every segmented driver.  None when no
    plan contributes a device-fused bias; otherwise one entry per
    segment: (n,) for a single-plan call, (n, B) zero-filled panels when
    several plans fuse different keyword queries in one batch.
    """
    per_plan = [plan_fusion_bias(p) for p in plans]
    if all(b is None for b in per_plan):
        return None
    if len(plans) == 1:
        ids, vals = per_plan[0]
        arrays, _ = store.score_bias_arrays(ids, vals, segments)
        return arrays
    out: List[Optional[np.ndarray]] = [None] * len(segments)
    for j, b in enumerate(per_plan):
        if b is None:
            continue
        cols, _ = store.score_bias_arrays(b[0], b[1], segments)
        for i, col in enumerate(cols):
            if col is None:
                continue
            if out[i] is None:
                out[i] = np.zeros((segments[i].n_rows, len(plans)),
                                  np.float32)
            out[i][:, j] = col
    return out


def finalize_fusion(
    plan: M.ModulationPlan,
    results: List[Tuple[int, float]],
    k: int,
    *,
    store=None,
    candidate_ids: Optional[Sequence[int]] = None,
) -> List[Tuple[int, float]]:
    """Host finishing stage for RANK fusion (``fuse:rrf,K``) — a no-op
    for every other plan.  RRF is not linear in scores, so it cannot ride
    the device bias: the device pass runs pure-vector, and this fuses its
    ranked list with the lexical list via ``modulations.rrf_fuse``.

    The lexical ids are clipped to the Phase-1 candidate set (the filter
    stays hard under fusion) and to live store membership (ids deleted
    since the FTS query — or FTS rows the vector store never held — are
    dropped, matching the non-strict prefilter contract).
    """
    f = plan.fusion
    if f is None or f.mode != "rrf" or plan.lexical is None:
        return results
    lex = np.asarray(plan.lexical.ids, np.int64)
    if candidate_ids is not None:
        cand = (candidate_ids if isinstance(candidate_ids, np.ndarray)
                else np.asarray(list(candidate_ids), dtype=np.int64))
        lex = lex[np.isin(lex, cand)]
    if store is not None:
        lex = np.asarray([i for i in lex if int(i) in store], np.int64)
    fused = M.rrf_fuse([i for i, _ in results], [int(i) for i in lex],
                       f.rrf_k)
    return [(int(i), float(s)) for i, s in fused[:max(0, k)]]


def finalize_segment_candidates(
    segments: Sequence,
    plans: Sequence[M.ModulationPlan],
    ks: Sequence[int],
    selected: Sequence[Candidates],
    *,
    mmr_done: bool = False,
    counters: Optional[FusedCounters] = None,
) -> List[List[Tuple[int, float]]]:
    """HOST TAIL of the segmented pipeline — the separable counterpart of
    :func:`score_select_segments` (the device pass).

    Takes the per-plan ``(global_rows, scores)`` candidates the device
    pass produced and finishes them on the host: truncate plain top-k,
    or — for diverse plans — gather the (pool,)-sized candidate
    embeddings and run the :func:`mmr_host` oracle over the oversampled
    pool, then resolve global rows to chunk ids.  Returns per-plan
    ``[(chunk_id, score), ...]`` descending — the shape every serving
    surface hands back.

    ``mmr_done=True`` declares that the device pass already finished
    diversity on device (``backend.device_mmr`` paths): diverse plans
    then truncate exactly like plain ones, and NO pool embedding gather
    happens at all — the pool never crossed the device boundary, and
    ``counters.host_pool_transfers`` stays untouched.

    Reads ONLY the immutable segment arrays of the snapshot it is given
    (sealed ids/matrix never change; compaction swaps the store's list
    but old segments stay valid), so it is safe to run WITHOUT the store
    lock, concurrently with the next batch's device pass — that overlap
    is the async engine's pipeline win.  Every consumer (direct
    ``VectorCache.search_plan``, the batched engine) calls this one
    function, so batched and direct rankings stay bit-identical.
    """
    from repro.core.segments import gather_ids, gather_rows

    out: List[List[Tuple[int, float]]] = []
    for plan, k, (gidx, vals) in zip(plans, ks, selected):
        if gidx.size == 0:
            out.append([])
            continue
        if plan.diverse is not None and not mmr_done:
            # host-oracle finishing: gather the oversample pool and run
            # mmr_host — the transfer the fused device paths avoid
            pool_emb = gather_rows(segments, gidx)
            loc, final_vals = finalize_candidates(
                pool_emb, np.arange(gidx.size, dtype=np.int64), vals, k,
                plan)
            if counters is not None:
                counters.host_pool_transfers += 1
            chunk_ids = gather_ids(segments, gidx[loc])
        else:
            # plain top-k — or a diverse plan the device already
            # finished — truncates; no pool embedding gather at all
            kf = max(0, min(k, int(gidx.size)))
            chunk_ids = gather_ids(segments, gidx[:kf])
            final_vals = vals[:kf]
        out.append([(int(i), float(v))
                    for i, v in zip(chunk_ids, final_vals)])
    return out


def select_candidates(
    matrix: np.ndarray,
    scores: np.ndarray,
    k: int,
    plan: M.ModulationPlan,
) -> np.ndarray:
    """Top-k (or MMR-diverse) row selection over a FULL host score array.

    The host-path reference for :meth:`ExecutionBackend.score_select` +
    :func:`finalize_candidates`; kept as the oracle the fused paths are
    pinned against.
    """
    n = scores.shape[0]
    k = min(k, n)
    if k <= 0:
        return np.empty(0, dtype=np.int64)
    if plan.diverse is not None:
        over = selection_width(plan, k, n)
        pool_idx = top_idx(scores, over)
        sel = mmr_host(matrix[pool_idx], scores[pool_idx], k,
                       plan.diverse.lam)
        return pool_idx[sel]
    return top_idx(scores, k)
