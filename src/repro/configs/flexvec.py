"""FLEXVEC itself as a servable architecture (the paper's system).

Cells lower the distributed Phase-2 engine: fused modulated scoring over a
row-sharded corpus matrix, streaming top-k, MMR diverse selection — i.e.
the TPU-native PEM retrieval kernel serving a BATCH of agent queries.

corpus_240k / corpus_1m mirror the paper's two headline corpus sizes
(§4.1/§4.3); corpus_67m is the beyond-paper scale point (256 chips x the
paper's 1M-chunk working set is pointless — scale the corpus instead:
67M chunks x 128d x f32 = 34 GB, row-sharded = 134 MB/chip).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ArchSpec, LoweredSpec, ShapeCell, with_sharding
from repro.dist.sharding import ShardingRules, default_rules
from repro.kernels.mmr.ref import mmr_ref
from repro.launch.mesh import make_local_mesh

SHAPES = {
    "corpus_240k": dict(n=240_000, batch=64, pool=500, over=1500),
    "corpus_1m": dict(n=1_000_000, batch=64, pool=500, over=1500),
    "corpus_67m": dict(n=67_108_864, batch=256, pool=500, over=1500),
}

DIM = 128  # Nomic Embed v1.5, Matryoshka-truncated (paper §2.1)


def pem_serve_step(corpus, days, q_pre, q_sup, *, pool: int, over: int):
    """The paper's Phase 2 as one jitted graph (pjit baseline path).

    scores = decay * (M @ q_pre) + M @ q_sup       (Table 1, fixed order)
    top-`over` pool -> MMR(lambda=0.7) -> `pool` selected ids + scores.
    On TPU the matmuls execute as the fused pem_score Pallas kernel; this
    pure-JAX body is the lowering used for dry-run/roofline (identical
    FLOP/byte profile).
    """
    decay = 1.0 / (1.0 + days / 30.0)
    scores = decay[:, None] * (corpus @ q_pre) + corpus @ q_sup   # (N, B)
    v, i = jax.lax.top_k(scores.T, over)                          # (B, over)
    emb = jnp.take(corpus, i, axis=0)                             # (B, over, d)
    sel, _ = mmr_ref(emb, v, pool)                                # diverse
    idx = jnp.take_along_axis(i, sel, axis=1)
    val = jnp.take_along_axis(v, sel, axis=1)
    return idx, val


class FlexvecArch(ArchSpec):
    family = "retrieval"

    def __init__(self, *, dtype=jnp.float32, mmr_vmem: bool = False,
                 two_stage: bool = False, arch_id: str = "flexvec"):
        """Hillclimb knobs (§Perf flexvec iterations):
        dtype     — corpus matrix dtype (bf16 halves the scoring stream);
        mmr_vmem  — account MMR with the Pallas kernel's VMEM-resident pool
                    (ONE HBM read) instead of the jnp fori_loop's per-
                    iteration re-read; the kernel is interpret-validated in
                    tests/test_kernels.py.
        two_stage — shard_map local-topk + union merge instead of the naive
                    pjit global top_k (which all-gathers the (N,B) scores)."""
        self.arch_id = arch_id
        self.source = "this paper"
        self.dtype = dtype
        self.mmr_vmem = mmr_vmem
        self.two_stage = two_stage
        # queries the MMR stage shards over (1 = replicated); §Perf flexvec-6
        self.mmr_shards = 1

    def cells(self) -> Dict[str, ShapeCell]:
        return {
            name: ShapeCell(
                name=name, kind="retrieval",
                desc=f"corpus={s['n']} queries={s['batch']} pool={s['pool']}",
                beyond_assignment=True,
            )
            for name, s in SHAPES.items()
        }

    def cost_corrections(self, shape: str, chips: int):
        """MMR's fori_loop body is counted once by cost_analysis; add the
        remaining (pool-1) iterations analytically (replicated per device):
        per iter per query: one-hot matmul (2*over*d) + sim matvec (2*over*d)
        + O(over) elementwise. With mmr_vmem the Pallas kernel keeps the pool
        resident in VMEM (2MB/query << 16MB), so HBM sees ONE pool read; the
        per-iteration traffic drops to the O(over) state vectors."""
        s = SHAPES[shape]
        b_local = max(1, s["batch"] // max(self.mmr_shards, 1))
        per_iter = b_local * (4.0 * s["over"] * DIM + 6.0 * s["over"])
        extra_flops = (s["pool"] - 1) * per_iter
        if self.mmr_vmem:
            extra_bytes = (s["pool"] - 1) * b_local * 3 * s["over"] * 4.0
        else:
            extra_bytes = (s["pool"] - 1) * b_local * (
                s["over"] * DIM * 4.0 + 3 * s["over"] * 4.0)
        return extra_flops, extra_bytes

    def model_flops(self, shape: str) -> float:
        s = SHAPES[shape]
        N, B, pool, over = s["n"], s["batch"], s["pool"], s["over"]
        scoring = 2.0 * N * DIM * B * 2          # two effective directions
        mmr = 2.0 * B * pool * over * DIM        # k x n pairwise updates
        return scoring + mmr

    def build(self, shape: str, mesh: Mesh, rules: ShardingRules) -> LoweredSpec:
        s = SHAPES[shape]
        N, B = s["n"], s["batch"]
        shards = max(rules.size_of("corpus"), 1)
        N = (N + shards - 1) // shards * shards  # pad rows to the shard grid
        corpus = with_sharding(
            jax.ShapeDtypeStruct((N, DIM), self.dtype),
            rules.spec("corpus", None), mesh)
        days = with_sharding(
            jax.ShapeDtypeStruct((N,), jnp.float32), rules.spec("corpus"), mesh)
        q_pre = with_sharding(
            jax.ShapeDtypeStruct((DIM, B), jnp.float32), rules.spec(None, None), mesh)
        q_sup = with_sharding(
            jax.ShapeDtypeStruct((DIM, B), jnp.float32), rules.spec(None, None), mesh)

        pool, over = s["pool"], s["over"]

        if self.two_stage:
            from repro.dist.pem_sharded import make_pem_topk

            local_topk = make_pem_topk(mesh, rules, over, raw=True)

            mmr_shards = self.mmr_shards

            def step(corpus, days, q_pre, q_sup):
                # stage 1: shard-local scoring + local top-over, union merge
                # (collective = shards*over*B candidates, NOT the N*B panel)
                i, v = local_topk(corpus, days, q_pre, q_sup)   # (B, over)
                # stage 2: gather pool embeddings + MMR diverse selection;
                # MMR queries are independent -> shard the batch instead of
                # replicating 500 iterations on every chip (flexvec-6)
                emb = jnp.take(corpus, i, axis=0)
                if mmr_shards > 1:
                    from jax.sharding import PartitionSpec as P
                    emb = jax.lax.with_sharding_constraint(
                        emb, P("data", None, None))
                    v = jax.lax.with_sharding_constraint(v, P("data", None))
                sel, _ = mmr_ref(emb, v, pool)
                idx = jnp.take_along_axis(i, sel, axis=1)
                val = jnp.take_along_axis(v, sel, axis=1)
                return idx, val
        else:
            def step(corpus, days, q_pre, q_sup):
                return pem_serve_step(corpus, days, q_pre, q_sup,
                                      pool=pool, over=over)

        return LoweredSpec(fn=step, args=(corpus, days, q_pre, q_sup),
                           static_desc=f"flexvec/{shape}")

    def smoke_run(self) -> Dict[str, Any]:
        mesh = make_local_mesh()
        rules = default_rules(mesh)
        with mesh:
            k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
            corpus = jax.random.normal(k1, (512, DIM))
            corpus = corpus / jnp.linalg.norm(corpus, axis=1, keepdims=True)
            days = jax.random.uniform(k2, (512,), minval=0.0, maxval=90.0)
            q = jax.random.normal(k3, (DIM, 2))
            idx, val = pem_serve_step(corpus, days, q, -0.5 * q, pool=8, over=24)
        return {
            "idx_shape": tuple(idx.shape),
            "val_finite": bool(jnp.isfinite(val).all()),
            "loss": float(val.mean()),
        }


FLEXVEC_ARCHS = [FlexvecArch()]
