"""The passage deployment (``msmarco_passage_768``) on the CPU: its corpus
module (the same rows at any thread count, unit rows with no timestamps,
queries with real neighbours, the reference's refusals), its three metric
readers on a constructed run, and a tiny traced run of its cell on four
forced host devices (in a subprocess: the flag must never reach this
process)."""

import json
import os
import subprocess
import sys
import textwrap
import tracemalloc
import types

import numpy as np
import pytest

from perfbench.lib import tracing
from perfbench.lib.bench import ROOT, Benchmark
from perfbench.lib.reference import unit
from repro.core.spans import Span

BENCH = Benchmark()
CELL = "msmarco768_search_closed64"
CFG = BENCH.config("msmarco_passage_768")
MOD = BENCH.corpus(CFG)
EMB = MOD.embedding(CFG)
NEW = ("merge_ms_per_pass.search", "shard_scan_roofline.search", "row_pad_share.search")


def test_passage_corpus_is_the_same_at_any_thread_count(monkeypatch):
    cfg = dict(CFG, rows=2 * MOD.BLOCK + 5)
    monkeypatch.setattr(MOD, "cores", lambda: 1)
    one = MOD.generate(cfg, 2**31 + 11, EMB).matrix
    monkeypatch.setattr(MOD, "cores", lambda: 8)
    eight = MOD.generate(cfg, 2**31 + 11, EMB).matrix
    assert one.tobytes() == eight.tobytes()
    other = MOD.generate(cfg, 2**31 + 12, EMB).matrix
    assert not np.array_equal(one, other)


def test_passage_block_allocates_nothing_of_its_size():
    """A block is written in place: its rows and the thread's buffer are
    the only arrays of its size (a per-block temporary, freed at once,
    still drove the host's memory in use to three times the rows' rate
    on the four-chip host)."""
    dim = int(EMB.dim)
    topics = MOD.topic_matrix(EMB)
    taper = np.ones(dim, np.float32)
    out = np.empty((MOD.BLOCK, dim), np.float32)
    scratch = np.empty_like(out)
    seed = np.random.SeedSequence(3)
    tracemalloc.start()
    MOD._fill_block(out, topics, taper, seed, scratch)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < out.nbytes / 16
    np.testing.assert_allclose(np.linalg.norm(out, axis=1), 1.0, rtol=1e-5)


def test_passage_rows_are_unit_with_no_timestamps_and_queries_have_neighbours():
    corpus = MOD.generate(dict(CFG, rows=20_003), 5, EMB)
    assert corpus.matrix.shape == (20_003, 768) and corpus.matrix.dtype == np.float32
    assert corpus.timestamps is None and corpus.n == 20_003
    np.testing.assert_array_equal(corpus.ids, np.arange(20_003))
    norms = np.linalg.norm(corpus.matrix.astype(np.float64), axis=1)
    assert np.abs(norms - 1.0).max() < 1e-5
    # a query of vocabulary words finds passages ten times the cosine
    # spread of unrelated 768-d unit vectors above the median row
    q = EMB(" ".join(MOD.VOCAB[j] for j in (3, 40, 700, 9000)))
    s = corpus.matrix @ q
    assert s.max() > np.median(s) + 10 / np.sqrt(768)


def test_passage_reference_scores_every_row_and_refuses_what_it_cannot_answer():
    corpus = MOD.generate(dict(CFG, rows=3_001), 9, EMB)
    ref = MOD.reference(corpus, EMB, "f64")
    spec = {"similar": "bakafa dabada", "suppress": ["zuzuzu"], "from": None,
            "decay": None, "hybrid": None, "filter": None, "diverse": False,
            "k": 10, "surface": "search"}
    scored = ref.score([spec])[0]
    m = corpus.matrix.astype(np.float64)
    want = m @ unit(EMB(spec["similar"])) - 0.5 * (m @ unit(EMB("zuzuzu")))
    np.testing.assert_allclose(scored.scores, want, rtol=0, atol=1e-12)
    assert scored.n_eligible == 3_001
    for extra in ({"decay": 30}, {"filter": {"type": "file"}}):
        with pytest.raises(ValueError):
            ref.score([dict(spec, **extra)])


def _sp(name, a, b):
    return Span(name, 0, None, None, "t", int(a * 1e9), int(b * 1e9), {})


def _run(scope_seconds, op_seconds, spans=True):
    trace = tracing.Trace(window=(1.0, 3.0), busy=[(1.0, 2.0)], op_seconds=op_seconds)
    spans = [_sp("engine.device", 1.1, 1.5), _sp("engine.device", 1.6, 2.9),
             _sp("engine.device", 2.9, 3.2)] if spans else None  # the last ends outside
    return types.SimpleNamespace(
        surface="search", trace=trace, spans=spans, scope_seconds=scope_seconds,
        device={"kind": "TPU v5 lite", "count": 4}, cfg={"rows": 8_000_000, "dim": 768},
        counters={"device_cache": {"rows_placed": 8_000_000, "row_grid": 8_000_000 + 80_000}})


def test_new_readers_on_a_constructed_run():
    ops = {"%all-gather.15 all-gather s32[128,2048]": 0.002,
           "%all-gather.17 all-gather f32[128,2048,768]": 0.010,
           "%sort.10 sort f32[4096,17408]": 0.5, "%fusion.29 fusion f32[32,2228224]": 0.3}
    read = {n: BENCH.reader(n).read for n in NEW}
    # two passes end in the slice: the all-gathers' 12 ms over them, the
    # same whether or not the run's scope reading names a merge scope
    assert read["merge_ms_per_pass.search"](_run({"score": 0.04}, ops)) == pytest.approx(6.0)
    assert read["merge_ms_per_pass.search"](
        _run({"score": 0.04, "merge": 0.03}, ops)) == pytest.approx(6.0)
    # 2 passes x 2M rows x 768 x 4 B at 819 GB/s over 40 ms of score
    least = 2 * 2_000_000 * 768 * 4 / 819e9
    assert read["shard_scan_roofline.search"](_run({"score": 0.04}, ops)) == pytest.approx(
        least / 0.04 * 100)
    assert read["row_pad_share.search"](_run({}, ops)) == pytest.approx(0.01)
    # an untraced run, a run with no collectives or no score scope: nothing
    bare = _run(None, {}, spans=False)
    bare.trace = None
    assert read["merge_ms_per_pass.search"](bare) is None
    assert read["shard_scan_roofline.search"](bare) is None
    assert read["merge_ms_per_pass.search"](_run({}, {"%fusion.1 fusion f32[8]": 1.0})) is None
    assert read["shard_scan_roofline.search"](_run({"select": 0.1}, ops)) is None
    bare.counters = {"device_cache": {"entries": 1}}  # a backend without the counters
    assert read["row_pad_share.search"](bare) is None


PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json, jax
    from perfbench import run as R
    assert jax.device_count() == 4
    res = R.run_cell("msmarco768_search_closed64", 2**31 + 29, 1.0, True,
                     device_check=lambda c: {"platform": "cpu", "kind": "cpu", "count": c},
                     config_overrides={"rows": 5_003})
    print("RESULT " + json.dumps({k: res[k] for k in ("correct", "attempted", "failed",
                                                       "metrics", "checks")}))
""")


def test_msmarco_cell_runs_traced_on_four_cpu_devices():
    """The cell, its rows cut to 5,003, traced through ``run_cell`` on four
    forced CPU devices: correct, no failures, the placement counter's
    metric read; the two device-trace metrics read nothing on the CPU
    (its trace has no TPU plane) and are read on a constructed run above."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(ROOT), str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", PROG], capture_output=True, text=True,
                       timeout=900, cwd=ROOT, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # 5,003 rows on the pow2 grid: 8,192 device rows
    assert res["metrics"]["row_pad_share.search"]["value"] == pytest.approx(8192 / 5003 - 1)
    assert not {"merge_ms_per_pass.search", "shard_scan_roofline.search"} & set(res["metrics"])
