"""Gradient accumulation: n_micro microbatches == one full-batch step."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import default_rules
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.models.layers import LMConfig
from repro.train.optimizer import (
    AdamWConfig,
    adamw_update,
    init_opt_state,
    make_grad_accum_step,
)


def test_accum_matches_full_batch():
    cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                   head_dim=16, d_ff=64, vocab=64, dtype=jnp.float32,
                   q_chunk=16, remat=False)
    mesh = make_local_mesh()
    rules = default_rules(mesh)
    params = T.init_params(cfg, jax.random.key(0))
    ocfg = AdamWConfig(lr=1e-3, clip_norm=None, compress_grads=False)

    toks = jax.random.randint(jax.random.key(1), (8, 16), 0, 64)
    batch = {"tokens": toks, "labels": toks}
    loss_fn = lambda p, b: T.lm_loss(p, b, cfg, rules)

    with mesh:
        # full batch
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        p_full, o_full, m_full = adamw_update(
            ocfg, params, grads, init_opt_state(params))
        # 4 microbatches of 2
        step = jax.jit(make_grad_accum_step(loss_fn, ocfg, n_micro=4))
        p_acc, o_acc, m_acc = step(params, init_opt_state(params), batch)

    np.testing.assert_allclose(float(m_acc["loss"]), float(loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p_acc), jax.tree.leaves(p_full)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-4)


def test_accum_trains():
    cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                   head_dim=16, d_ff=64, vocab=64, dtype=jnp.float32,
                   q_chunk=16, remat=False)
    mesh = make_local_mesh()
    rules = default_rules(mesh)
    params = T.init_params(cfg, jax.random.key(0))
    ocfg = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=30)
    loss_fn = lambda p, b: T.lm_loss(p, b, cfg, rules)
    step = jax.jit(make_grad_accum_step(loss_fn, ocfg, n_micro=2))
    opt = init_opt_state(params)
    toks = jax.random.randint(jax.random.key(1), (8, 16), 0, 64)
    batch = {"tokens": toks, "labels": toks}
    losses = []
    with mesh:
        for _ in range(20):
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5
