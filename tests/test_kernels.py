"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.modulations import mmr_select_np
from repro.kernels.mmr.ops import mmr_select
from repro.kernels.mmr.ref import mmr_ref
from repro.kernels.pem_score.ops import pem_score
from repro.kernels.pem_score.ref import pem_score_ref

RNG = np.random.default_rng(0)


def _corpus(n, d, dtype):
    m = RNG.standard_normal((n, d)).astype(np.float32)
    m /= np.linalg.norm(m, axis=1, keepdims=True)
    return jnp.asarray(m, dtype=dtype)


@pytest.mark.parametrize("n", [100, 1000, 2049])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("b", [1, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pem_score_sweep(n, d, b, dtype):
    m = _corpus(n, d, dtype)
    qp = jnp.asarray(RNG.standard_normal((d, b)).astype(np.float32))
    qs = jnp.asarray(RNG.standard_normal((d, b)).astype(np.float32) * 0.3)
    decay = jnp.asarray((1.0 / (1.0 + RNG.random(n) * 10)).astype(np.float32))
    out = pem_score(m, qp, qs, decay, interpret=True, block_n=256, block_b=128)
    ref = pem_score_ref(m, qp, qs, decay)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2   # bf16 inputs, f32 accum
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol, rtol=tol)


def test_pem_score_no_decay():
    m = _corpus(500, 128, jnp.float32)
    qp = jnp.asarray(RNG.standard_normal((128, 3)).astype(np.float32))
    qs = jnp.zeros((128, 3), jnp.float32)
    out = pem_score(m, qp, qs, None, interpret=True, block_n=256)
    np.testing.assert_allclose(np.asarray(out), np.asarray(m @ qp), atol=1e-5)


@pytest.mark.parametrize("n,k,d", [(64, 8, 32), (200, 50, 128), (300, 17, 64)])
def test_mmr_sweep(n, k, d):
    e = RNG.standard_normal((2, n, d)).astype(np.float32)
    e /= np.linalg.norm(e, axis=-1, keepdims=True)
    rel = RNG.standard_normal((2, n)).astype(np.float32)
    ik, vk = mmr_select(jnp.asarray(e), jnp.asarray(rel), k, 0.7, interpret=True)
    ir, vr = mmr_ref(jnp.asarray(e), jnp.asarray(rel), k, 0.7)
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
    for b in range(2):
        np_sel = mmr_select_np(e[b], rel[b], k, 0.7)
        np.testing.assert_array_equal(np.asarray(ik[b]), np_sel)


def test_mmr_lambda_extremes():
    e = RNG.standard_normal((1, 60, 16)).astype(np.float32)
    e /= np.linalg.norm(e, axis=-1, keepdims=True)
    rel = RNG.standard_normal((1, 60)).astype(np.float32)
    # lam=1.0 -> pure relevance order == topk order
    ik, _ = mmr_select(jnp.asarray(e), jnp.asarray(rel), 10, 1.0, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(ik[0]), np.argsort(-rel[0], kind="stable")[:10])


def test_fold_plan_matches_modulation_pipeline():
    """kernel-input folding (q_pre/q_sup) == the paper's fixed-order math."""
    from repro.core import modulations as M
    from repro.core.grammar import parse
    from repro.embed import HashEmbedder
    from repro.kernels.pem_score.ops import fold_plan

    emb = HashEmbedder(128)
    mat = _corpus(400, 128, jnp.float32)
    days = np.abs(RNG.standard_normal(400)).astype(np.float32) * 30
    plan = parse("similar:alpha beta from:old to:new decay:14 "
                 "suppress:noise one suppress:noise two", emb)
    q_pre, q_sup = fold_plan(plan)
    decay = (1.0 / (1.0 + days / 14.0)).astype(np.float32)
    fused = decay * (np.asarray(mat) @ q_pre) + np.asarray(mat) @ q_sup
    ref = M.modulate_scores(np.asarray(mat), days, plan)
    np.testing.assert_allclose(fused, np.asarray(ref), atol=1e-5)
