"""chip_smoke.py's phases at a tiny size on the CPU (Pallas interpret mode
on, as tests/conftest.py sets it), its refusal to report off a TPU, its
oracle-matching rule, and the compile-cache placement it relies on."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.backends import PallasBackend
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve their module here
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop(spec.name, None)


@pytest.fixture(scope="module")
def conn(smoke):
    return smoke.build_corpus(n_chunks=600, n_sessions=20)


def test_served_phase_matches_oracle(smoke, conn):
    records = smoke.phase_served(conn, smoke.DEVICE_BACKENDS, n_concurrent=8)
    assert [r.engine for r in records] == list(smoke.DEVICE_BACKENDS)
    for rec in records:
        assert set(rec.answers) == {"composed", "filtered", "hybrid",
                                    "search_x8"}
        assert all(rows > 0 for rows, _, _ in rec.answers.values())
        assert rec.uploads >= 1 and rec.batches >= 1
        assert len(rec.lines()) == 5
    jit, pallas = records
    assert jit.traces >= 1
    # interpret mode lowers to plain HLO: the check main() enforces on a
    # TPU reads False here, which is exactly what it must catch
    assert pallas.pallas_compiled is False


def test_scale_phase_matches_oracle(smoke, conn):
    engines = ("sharded", "jit-jax", "pallas")
    records = smoke.phase_scale(conn, 1500, engines, batch=4)
    assert [r.engine for r in records] == list(engines)
    for rec in records:
        assert set(rec.answers) == {"composed", "batch_x4"}
        assert rec.answers["composed"][0] > 0


def test_main_refuses_a_cpu_run(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert "'cpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_check_answer_rule(smoke):
    want = [(1, 0.9), (2, 0.5), (3, 0.5 - 1e-7), (4, 0.1)]
    # a near tie broken the other way is a counted swap
    got = [(1, 0.9), (3, 0.5 - 1e-7), (2, 0.5), (4, 0.1)]
    assert smoke.check_answer("t", got, want, 1e-5) == (2, 0.0)
    # ... and so is a near tie straddling the cut, given the longer oracle
    cut = [(1, 0.9), (3, 0.5 - 1e-7)]
    assert smoke.check_answer("t", cut, want[:2], 1e-5, want)[0] == 1
    # a row the oracle ranks elsewhere fails, naming both scores
    with pytest.raises(smoke.Mismatch, match="score 0.1.*score 0.5"):
        smoke.check_answer("t", [(1, 0.9), (4, 0.1), (2, 0.5), (3, 0.5)],
                           want, 1e-5)
    with pytest.raises(smoke.Mismatch, match="differs"):
        smoke.check_answer("t", [(1, 0.9 + 1e-3)] + want[1:], want, 1e-5)

    # an MMR-ordered answer ties on its objective, not on relevance: pick
    # 2 (rel 0.5, sim 0.7/1.5 to pick 1) and pick 3 (rel 0.3, orthogonal)
    # score 0.21 each at step 2 under lam = 0.7
    s = 0.14 / 0.3
    embeds = np.array([[1.0, 0.0, 0.0], [s, np.sqrt(1 - s * s), 0.0],
                       [0.0, 0.0, 1.0]])
    mmr = [(1, 0.9), (2, 0.5), (3, 0.3)]
    keys = smoke.mmr_keys(mmr, embeds, 0.7)
    np.testing.assert_allclose(keys[1:], [0.21, 0.21])
    flipped = [(1, 0.9), (3, 0.3), (2, 0.5)]
    assert smoke.check_answer("t", flipped, mmr, 1e-5, keys=keys)[0] == 2
    with pytest.raises(smoke.Mismatch):
        smoke.check_answer("t", flipped, mmr, 1e-5)


def test_pallas_without_interpret_fails_on_cpu():
    """No fallback hides the device: a served-path pallas backend (the
    class default, interpret off) refuses to run its kernels on the CPU
    instead of quietly interpreting them."""
    mat = np.eye(8, 128, dtype=np.float32)
    backend = PallasBackend()
    assert backend.interpret is False
    from repro.core.grammar import parse
    from repro.embed import HashEmbedder

    plan = parse("similar:anything", HashEmbedder(128))
    with pytest.raises(Exception, match="(?i)interpret|tpu|mosaic"):
        backend.score_select(mat, None, [plan], [3])


def test_compile_cache_honours_the_environment(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
