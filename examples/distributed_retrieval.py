"""Distributed PEM: row-sharded corpus scoring with local-topk + global merge.

Runs on 8 forced host devices (this script sets the flag BEFORE importing
jax — same pattern as launch/dryrun.py) and verifies the sharded result
against the unsharded oracle, then shows the collective-byte math that makes
this the §Perf "flexvec-1" iteration.

    PYTHONPATH=src python examples/distributed_retrieval.py
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"  # 8 host devices, never a TPU

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.pem_sharded import make_pem_topk, pem_topk_reference
from repro.dist.sharding import default_rules

N, D, B, K = 262_144, 128, 16, 500


def main() -> None:
    print(f"== devices: {jax.device_count()} (forced host platform)")
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    rules = default_rules(mesh)

    rng = np.random.default_rng(0)
    corpus = rng.standard_normal((N, D)).astype(np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    corpus = jnp.asarray(corpus)
    days = jnp.asarray(rng.uniform(0, 90, N).astype(np.float32))
    q_pre = jnp.asarray(rng.standard_normal((D, B)).astype(np.float32))
    q_sup = jnp.asarray(-0.5 * rng.standard_normal((D, B)).astype(np.float32))

    sharded = make_pem_topk(mesh, rules, K)
    t0 = time.time()
    idx_s, val_s = jax.block_until_ready(sharded(corpus, days, q_pre, q_sup))
    t_first = time.time() - t0
    t0 = time.time()
    idx_s, val_s = jax.block_until_ready(sharded(corpus, days, q_pre, q_sup))
    t_warm = time.time() - t0

    idx_r, val_r = pem_topk_reference(corpus, days, q_pre, q_sup, K)
    # per-shard vs full-matrix matmul reassociation leaves ~1e-7 score noise;
    # at 262k rows that can swap ADJACENT ranks of fp-tied scores, so compare
    # the candidate sets + values, not the exact order
    idx_s_np, idx_r_np = np.asarray(idx_s), np.asarray(idx_r)
    sets_ok = all(set(idx_s_np[b]) == set(idx_r_np[b]) for b in range(B))
    vals_ok = np.allclose(np.asarray(val_s), np.asarray(val_r), rtol=1e-5)
    ok = sets_ok and vals_ok
    print(f"== sharded == unsharded oracle: {ok} "
          f"(candidate sets equal: {sets_ok}, values rtol=1e-5: {vals_ok})")
    print(f"   first call {t_first*1e3:.1f} ms (compile), warm {t_warm*1e3:.1f} ms")

    shards = 4  # corpus axis = 'data'
    naive = N * B * 4
    ours = shards * K * B * 8 * 2
    print(f"   naive pjit top-k all-gathers the scores: {naive/1e6:.1f} MB")
    print(f"   local-topk union all-gather:             {ours/1e6:.3f} MB "
          f"({naive/ours:.0f}x less collective traffic)")


if __name__ == "__main__":
    main()
