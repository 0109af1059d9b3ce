"""Training loop + fault tolerance: loss goes down, resume is exact,
stragglers are flagged, elastic replanning works."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.loader import LMDataConfig, SyntheticLMStream
from repro.dist.sharding import default_rules
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.models.layers import LMConfig
from repro.train import checkpoint as C
from repro.train.elastic import ElasticPlan, StepWatchdog, replan_mesh
from repro.train.loop import TrainLoopConfig, Trainer
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def _setup(tmp_path=None, seed=0):
    mesh = make_local_mesh()
    rules = default_rules(mesh)
    cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                   head_dim=16, d_ff=64, vocab=64, dtype=jnp.float32,
                   q_chunk=16, remat=False)
    params = T.init_params(cfg, jax.random.key(seed))
    opt = init_opt_state(params)
    ocfg = AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=100)

    def step_fn(params, opt_state, batch):
        loss, grads = jax.value_and_grad(T.lm_loss)(params, batch, cfg, rules)
        params, opt_state, metrics = adamw_update(ocfg, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **metrics}

    step_fn = jax.jit(step_fn)
    stream = SyntheticLMStream(LMDataConfig(vocab=64, batch=8, seq_len=32))
    to_batch = lambda b: {k: jnp.asarray(v) for k, v in b.items()}
    lcfg = TrainLoopConfig(
        total_steps=40, ckpt_every=10, log_every=5,
        ckpt_dir=str(tmp_path) if tmp_path else None)
    trainer = Trainer(step_fn, params, opt, stream, lcfg, to_batch)
    return mesh, trainer


def test_loss_decreases():
    mesh, trainer = _setup()
    with mesh:
        out = trainer.run(40)
    first = out["history"][0]["loss"]
    last = out["history"][-1]["loss"]
    assert last < first - 0.15, (first, last)


def test_resume_is_exact(tmp_path):
    # continuous reference: 30 uninterrupted steps
    mesh, ref_t = _setup(tmp_path / "ref")
    with mesh:
        ref = ref_t.run(30)

    # interrupted run: 20 steps, then "node failure"
    mesh, t1 = _setup(tmp_path / "a")
    with mesh:
        t1.run(20)
        t1.ckpt.wait()

    # restart: fresh trainer (DIFFERENT init seed) restores params, opt
    # state, and data-iterator state from the checkpoint
    mesh, t2 = _setup(tmp_path / "a", seed=123)
    assert t2.try_resume()
    assert t2.step == 20
    with mesh:
        out = t2.run(10)
    np.testing.assert_allclose(out["final_loss"], ref["final_loss"], rtol=1e-4)


def test_no_resume_without_ckpt(tmp_path):
    mesh, t = _setup(tmp_path / "empty")
    assert not t.try_resume()


def test_watchdog_flags_stragglers():
    w = StepWatchdog(warmup=3)
    for _ in range(10):
        w.observe(0.1)
    assert w.observe(1.5)                 # 15x slower -> straggler
    assert len(w.events) == 1
    assert not w.observe(0.1)


def test_elastic_replan():
    assert replan_mesh(512, 16) == (32, 16)
    assert replan_mesh(496, 16) == (31, 16)
    plan = ElasticPlan.on_failure(512, 16, model_parallel=16)
    assert plan.new_devices == 496 and plan.mesh_shape == (31, 16)
    with pytest.raises(ValueError):
        replan_mesh(8, 16)


def test_checkpoint_prune_and_latest(tmp_path):
    tree = {"x": np.ones(3)}
    for s in (1, 2, 3, 4, 5):
        C.save(tmp_path, s, tree)
    C.prune(tmp_path, keep=2)
    assert C.latest_step(tmp_path) == 5
    assert sorted(p.name for p in tmp_path.glob("ckpt_*.npz")) == [
        "ckpt_4.npz", "ckpt_5.npz"]


def test_checkpoint_shape_mismatch_is_loud(tmp_path):
    C.save(tmp_path, 1, {"x": np.ones(3)})
    with pytest.raises(ValueError):
        C.restore(tmp_path, {"x": np.ones(4)})


def test_data_stream_seekable():
    cfg = LMDataConfig(vocab=64, batch=4, seq_len=16, seed=3)
    a = SyntheticLMStream(cfg)
    b1 = [a.next_batch() for _ in range(5)]
    b = SyntheticLMStream(cfg)
    b.load_state_dict({"step": 3})
    np.testing.assert_array_equal(b.next_batch()["tokens"], b1[3]["tokens"])
