"""The served path on the ``sharded`` backend at 768-d: the row grid,
placement without a host copy of the whole matrix, the ``merge`` scope and
the placement counters, and ``BatchedRetrievalEngine`` answers against the
float64 reference and ``reference-numpy``.

The multi-device checks run on 4 forced host devices in a subprocess (the
flag must never reach this process)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import grammar
from repro.core.backends import (ROW_GRID_POW2_MAX, ROW_GRID_STEP, PlanStructure,
                                 _pow2_bucket, _row_grid)

ROOT = Path(__file__).resolve().parents[1]

# the corpora and every Phase-1 sub-corpus the benchmark's 128-d cells
# score (240k rows at 2.5%-45% selectivity, 1M rows), and the edges
EXISTING = [1, 2, 3, 5_003, 6_000, 24_000, 60_000, 108_000, 240_000,
            262_144, 262_145, 1_000_000, ROW_GRID_POW2_MAX]


@pytest.mark.parametrize("n", EXISTING)
def test_row_grid_is_the_pow2_bucket_up_to_2_20(n):
    plan = grammar.parse("similar:a b c diverse", lambda t: np.ones(8, np.float32))
    old = max(_pow2_bucket(n), 1)
    assert _row_grid(n) == old
    st = PlanStructure.of([plan] * 3, [1500] * 3, n, ks=[10] * 3,
                          device_mmr=True, cohort=True)
    assert st == PlanStructure(batch=4, n_rows=old, has_decay=False, suppress_bucket=0,
                               width=min(2048, old), mmr_k=min(16, old))


@pytest.mark.parametrize("n", [ROW_GRID_POW2_MAX + 1, 8_841_823, 8_912_896, 27_000_001])
def test_row_grid_above_2_20_is_a_step_multiple(n):
    g = _row_grid(n)
    assert g % ROW_GRID_STEP == 0 and g % (4 * 8) == 0
    assert n <= g < n + ROW_GRID_STEP
    if n == 8_841_823:
        assert g == 8_912_896 < _pow2_bucket(n) == 16_777_216


PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import asyncio, tracemalloc
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import grammar
    from repro.core.backends import (JitJaxBackend, PlanStructure, ShardedBackend,
                                     _row_grid)
    from repro.core.vectorcache import VectorCache
    from repro.serve.engine import BatchedRetrievalEngine
    from perfbench.corpora import msmarco_passage as mp
    from perfbench.lib import check
    from perfbench.requests import passage_search

    assert jax.device_count() == 4
    N = 3_001  # neither a multiple of 4 nor near a power of two
    cfg = {"rows": N, "dim": 768}
    emb = mp.embedding(cfg)
    corpus = mp.generate(cfg, 2**31 + 3, emb)
    ref = mp.reference(corpus, emb, "f64")

    # placement: each device gets its row slice; the only host copy is
    # the last shard, padded past the true rows
    big = np.ascontiguousarray(np.tile(corpus.matrix, (10, 1)))   # 30,010 rows
    rows = _row_grid(big.shape[0])
    shard_bytes = rows // 4 * 768 * 4
    tracemalloc.start()
    dev = ShardedBackend()._device_matrix(big, rows - big.shape[0])
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert dev.shape == (rows, 768) and len(dev.sharding.device_set) == 4
    assert peak <= shard_bytes + (1 << 20), (peak, shard_bytes)
    np.testing.assert_array_equal(np.asarray(dev)[:big.shape[0]], big)
    assert not np.asarray(dev)[big.shape[0]:].any()
    # the spy sees numpy copies: the single-device path pads on the host
    tracemalloc.start()
    JitJaxBackend()._device_matrix(big, rows - big.shape[0])
    _, peak_jit = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak_jit >= rows * 768 * 4, peak_jit

    # the merge scope names the union merge's ops
    st = PlanStructure(batch=4, n_rows=4096, has_decay=False, suppress_bucket=1,
                       width=2048, mmr_k=16)
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt)
    hlo = ShardedBackend()._build_select(st).lower(
        S((4096, 768)), S((768, 4)), S((768, 4)), S((4096,)), S((4,)),
        S((4096,), jnp.bool_), S((4,)), S((4,), jnp.int32), S((1, 1))).as_text(debug_info=True)
    assert "merge/all_gather" in hlo and "merge/top_k" in hlo
    assert "select/merge" not in hlo

    def engine(name):
        cache = VectorCache(corpus.ids, corpus.matrix, None, emb, normalized=True)
        return BatchedRetrievalEngine(cache, max_batch=32, max_wait_ms=500.0, now=0.0,
                                      engine=name, adaptive_window=False)

    traffic = {"k": 10}
    entries = [{"name": "similar", "similar": {"words": [3, 10]}},
               {"name": "suppress", "similar": {"words": [3, 10]},
                "suppress": [{"words": [2, 4]}]},
               {"name": "diverse", "similar": {"words": [3, 10]}, "diverse": True}]
    rng = np.random.default_rng(5)
    specs = [passage_search.make(rng, entries[j % 3], traffic) for j in range(32)]
    cohorts = [[s] for s in specs[:3]] + [specs[3:6], specs]

    async def serve(eng, cohort):
        return await asyncio.gather(*(eng.asearch(s["text"], s["k"]) for s in cohort))

    for name in ("sharded", "reference-numpy"):
        eng = engine(name)
        try:
            for cohort in cohorts:
                before = eng.stats()["batches_served"]
                got = asyncio.run(serve(eng, cohort))
                assert eng.stats()["batches_served"] == before + 1, "one cohort, one pass"
                for spec, ans in zip(cohort, got):
                    spec.setdefault("got", {})[name] = [(int(i), float(v)) for i, v in ans]
            if name == "sharded":
                stats = eng.backend.device_cache_stats()
        finally:
            eng.close()
    assert stats["rows_placed"] == N and stats["row_grid"] == 4096, stats

    worst = {k: 0.0 for k in check.NUMBERS}
    for spec, scored in zip(specs, ref.score(specs)):
        a, b = spec["got"]["sharded"], spec["got"]["reference-numpy"]
        assert [i for i, _ in a] == [i for i, _ in b], spec["tokens"]
        np.testing.assert_allclose([s for _, s in a], [s for _, s in b], rtol=0, atol=1e-5)
        for k, v in check.compare(ref, spec, scored, a).items():
            worst[k] = max(worst[k], v)
    assert worst["unanswered"] == 0 and worst["set_miss"] == 0, worst
    assert worst["score_gap"] <= 1e-5 and worst["rank_gap"] <= 1e-5, worst
    print("SHARDED_768_OK", sorted({s["kind"] for s in specs}))
""")


def test_sharded_served_path_at_768d_on_four_devices():
    """Cohorts of 1, 3 and 32 plain, suppress and diverse searches through
    ``BatchedRetrievalEngine`` on ``sharded`` over 4 devices: the same ids
    as ``reference-numpy``, and the float64 reference's rank and score
    checks; the placement copies at most one shard on the host; the union
    merge runs under ``merge``; the counters give true and device rows."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(ROOT / "src"), str(ROOT), os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", PROG], capture_output=True, text=True,
                       timeout=600, cwd=ROOT, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_768_OK ['diverse', 'similar', 'suppress']" in r.stdout
