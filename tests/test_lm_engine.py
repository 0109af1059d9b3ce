"""Continuous-batching LM decode engine."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import default_rules
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.models.layers import LMConfig
from repro.serve.lm_engine import DecodeRequest, LMDecodeEngine


def _engine(n_slots=3, max_ctx=48):
    cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                   head_dim=16, d_ff=64, vocab=64, dtype=jnp.float32,
                   q_chunk=16, remat=False)
    mesh = make_local_mesh()
    rules = default_rules(mesh)
    params = T.init_params(cfg, jax.random.key(0))
    return mesh, cfg, rules, params, LMDecodeEngine(
        cfg, params, rules, n_slots=n_slots, max_ctx=max_ctx)


def test_continuous_batching_serves_more_requests_than_slots():
    mesh, cfg, rules, params, eng = _engine(n_slots=2)
    rng = np.random.default_rng(0)
    reqs = [DecodeRequest(prompt=rng.integers(0, 64, 5).astype(np.int32),
                          max_new_tokens=4) for _ in range(5)]
    with mesh:
        stats = eng.run(reqs)
    assert stats["requests"] == 5            # 5 requests through 2 slots
    assert all(r.done for r in reqs)
    # prefill emits 1 token, then max_new_tokens decode steps
    for r in reqs:
        assert len(r.tokens) == 1 + 4
    assert 1.0 <= stats["mean_occupancy"] <= 2.0


def test_engine_matches_sequential_decode():
    """Tokens from the slot engine == naive one-request-at-a-time decode."""
    mesh, cfg, rules, params, eng = _engine(n_slots=2)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, 6).astype(np.int32) for _ in range(2)]
    reqs = [DecodeRequest(prompt=p, max_new_tokens=3) for p in prompts]
    with mesh:
        eng.run(list(reqs))

        for p, r in zip(prompts, reqs):
            logits, cache = T.prefill_step(params, jnp.asarray(p[None]), cfg, rules)
            big = T.make_cache(cfg, 1, 48)
            big = tuple(jax.lax.dynamic_update_slice(b, c, (0, 0, 0, 0, 0))
                        for b, c in zip(big, cache))
            toks = [int(jnp.argmax(logits[0]))]
            ln = len(p)
            for _ in range(3):
                lg, big = T.decode_step(
                    params, jnp.asarray([[toks[-1]]], jnp.int32), big,
                    jnp.int32(ln), cfg, rules)
                toks.append(int(jnp.argmax(lg[0])))
                ln += 1
            assert r.tokens == toks, (r.tokens, toks)


def test_eos_frees_slot_early():
    mesh, cfg, rules, params, eng = _engine(n_slots=1)
    rng = np.random.default_rng(2)
    # find which token the model emits first, use it as EOS for req 1
    probe = DecodeRequest(prompt=rng.integers(0, 64, 4).astype(np.int32),
                          max_new_tokens=2)
    with mesh:
        eng.run([probe])
        eos = probe.tokens[1]
        req = DecodeRequest(prompt=probe.prompt.copy(), max_new_tokens=8,
                            eos_id=eos)
        stats = eng.run([req])
    assert req.done
    assert len(req.tokens) < 1 + 8            # stopped early on EOS
