"""The four assigned recsys architectures.

Shapes (assignment):
    train_batch    batch=65,536        -> train_step
    serve_p99      batch=512           -> serve_step (forward)
    serve_bulk     batch=262,144       -> serve_step (offline scoring)
    retrieval_cand batch=1, 1M cands   -> retrieval scoring. For two-tower
                   this is the paper's PEM surface (modulated scoring +
                   top-k + MMR over a 1M-row candidate matrix); for the
                   pointwise CTR models it lowers bulk candidate scoring.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ArchSpec, LoweredSpec, ShapeCell, with_sharding
from repro.data import recsys as RD
from repro.data.recsys import CRITEO_1TB_VOCAB_SIZES
from repro.dist.sharding import ShardingRules, constrain, default_rules
from repro.kernels.mmr.ref import mmr_ref
from repro.launch.mesh import make_local_mesh
from repro.models import recsys as R
from repro.train.optimizer import AdamWConfig, OptState, adamw_update, init_opt_state

SHAPES = {
    "train_batch": dict(kind="train", batch=65_536),
    "serve_p99": dict(kind="serve", batch=512),
    "serve_bulk": dict(kind="serve", batch=262_144),
    "retrieval_cand": dict(kind="retrieval", batch=1, n_candidates=1_000_000),
}


class RecsysArch(ArchSpec):
    family = "recsys"

    def __init__(self, arch_id: str, source: str, cfg, init_fn, loss_fn,
                 fwd_fn, batch_fn, shardings_fn, smoke_cfg):
        self.arch_id = arch_id
        self.source = source
        self.cfg = cfg
        self.smoke_cfg = smoke_cfg
        self._init = init_fn
        self._loss = loss_fn
        self._fwd = fwd_fn
        self._batch = batch_fn           # (cfg, batch_size) -> struct dict+specs
        self._shardings = shardings_fn   # (cfg, rules) -> param spec tree

    def cells(self) -> Dict[str, ShapeCell]:
        out = {}
        for name, s in SHAPES.items():
            desc = f"batch={s['batch']}"
            if name == "retrieval_cand":
                desc += f" n_candidates={s['n_candidates']}"
                if self.arch_id != "two-tower-retrieval":
                    desc += " (pointwise CTR: lowered as bulk candidate scoring)"
            out[name] = ShapeCell(name=name, kind=s["kind"], desc=desc)
        return out

    def model_flops(self, shape: str) -> float:
        s = SHAPES[shape]
        if shape == "retrieval_cand" and self.arch_id == "two-tower-retrieval":
            # step scores a PRECOMPUTED candidate matrix: dot per candidate
            # + one user tower + MMR over the oversample pool (B=1)
            D = self.cfg.tower_mlp[-1]
            dims = (2 * self.cfg.embed_dim,) + self.cfg.tower_mlp
            tower = sum(2.0 * a * b for a, b in zip(dims[:-1], dims[1:]))
            return (2.0 * s["n_candidates"] * D + tower
                    + 2.0 * 500 * 1500 * D)
        b = s["batch"] if shape != "retrieval_cand" else s["n_candidates"]
        per_ex = _flops_per_example(self.arch_id, self.cfg)
        mult = 3.0 if s["kind"] == "train" else 1.0
        return mult * per_ex * b

    def cost_corrections(self, shape: str, chips: int):
        if shape == "retrieval_cand" and self.arch_id == "two-tower-retrieval":
            D = self.cfg.tower_mlp[-1]
            pool, over, b = 500, 1500, 1
            per_iter = b * (4.0 * over * D + 6.0 * over)
            return (pool - 1) * per_iter, (pool - 1) * b * over * D * 4.0
        return 0.0, 0.0

    def build(self, shape: str, mesh: Mesh, rules: ShardingRules) -> LoweredSpec:
        s = SHAPES[shape]
        cfg = self.cfg
        p_struct = jax.eval_shape(lambda: self._init(cfg, jax.random.key(0)))
        p_spec = self._shardings(cfg, rules)
        params = with_sharding(p_struct, p_spec, mesh)

        if shape == "retrieval_cand" and self.arch_id == "two-tower-retrieval":
            return self._build_retrieval(s, mesh, rules, params, p_struct)

        batch_size = s["batch"] if shape != "retrieval_cand" else s["n_candidates"]
        batch_struct, batch_spec = self._batch(cfg, batch_size)
        batch = with_sharding(batch_struct, batch_spec(rules), mesh)

        if s["kind"] == "train":
            o_struct = jax.eval_shape(init_opt_state, p_struct)
            opt = with_sharding(
                o_struct,
                OptState(step=rules.spec(), m=p_spec, v=jax.tree.map(lambda x: x, p_spec)),
                mesh,
            )
            ocfg = AdamWConfig()
            loss_fn = self._loss

            def train_step(params, opt_state, batch):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg, rules)
                params, opt_state, metrics = adamw_update(ocfg, params, grads, opt_state)
                return params, opt_state, {"loss": loss, **metrics}

            return LoweredSpec(fn=train_step, args=(params, opt, batch),
                               donate_argnums=(0, 1),
                               static_desc=f"{self.arch_id}/{shape}")

        fwd = self._fwd

        def serve_step(params, batch):
            return fwd(params, batch, cfg, rules)

        return LoweredSpec(fn=serve_step, args=(params, batch),
                           static_desc=f"{self.arch_id}/{shape}")

    def _build_retrieval(self, s, mesh, rules, params, p_struct) -> LoweredSpec:
        """Two-tower retrieval_cand: the paper's Phase-2 on 1M candidates."""
        cfg = self.cfg
        shards = max(rules.size_of("candidates"), 1)
        N = (s["n_candidates"] + shards - 1) // shards * shards  # pad to shard
        D = cfg.tower_mlp[-1]
        batch_struct = {
            "user_id": jax.ShapeDtypeStruct((1,), jnp.int32),
            "hist": jax.ShapeDtypeStruct((1, cfg.hist_len), jnp.int32),
        }
        bspec = {"user_id": rules.spec(None), "hist": rules.spec(None, None)}
        batch = with_sharding(batch_struct, bspec, mesh)
        cand = with_sharding(
            jax.ShapeDtypeStruct((N, D), jnp.float32),
            rules.spec("candidates", None), mesh)
        days = with_sharding(
            jax.ShapeDtypeStruct((N,), jnp.float32), rules.spec("candidates"), mesh)
        pool, over = 500, 1500

        def retrieval_step(params, batch, cand, days):
            # PEM fixed order on candidate scores: similarity -> decay -> MMR
            scores = R.retrieval_scores(params, batch, cand, cfg, rules)  # (N, B)
            scores = scores * (1.0 / (1.0 + days / 30.0))[:, None]        # decay:30
            v, i = jax.lax.top_k(scores.T, over)                          # (B, over)
            emb = jnp.take(cand, i, axis=0)                               # (B, over, D)
            sel, mmr_scores = mmr_ref(emb, v, pool)                       # diverse
            final_idx = jnp.take_along_axis(i, sel, axis=1)
            final_scores = jnp.take_along_axis(v, sel, axis=1)
            return final_idx, final_scores

        return LoweredSpec(fn=retrieval_step, args=(params, batch, cand, days),
                           static_desc=f"{self.arch_id}/retrieval_cand")

    def smoke_run(self) -> Dict[str, Any]:
        mesh = make_local_mesh()
        rules = default_rules(mesh)
        cfg = self.smoke_cfg
        with mesh:
            params = self._init(cfg, jax.random.key(0))
            batch_struct, _ = self._batch(cfg, 16)
            data = _smoke_data(self.arch_id, cfg, 16)
            loss, grads = jax.value_and_grad(self._loss)(params, data, cfg, rules)
            fwd_out = self._fwd(params, data, cfg, rules)
        return {
            "loss": float(loss),
            "grad_finite": all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(grads)),
            "fwd_shape": tuple(jnp.asarray(fwd_out).shape),
        }


def _flops_per_example(arch_id: str, cfg) -> float:
    """Analytic forward FLOPs per example (matmul-dominated terms)."""
    def mlp_flops(dims):
        return sum(2.0 * a * b for a, b in zip(dims[:-1], dims[1:]))

    if arch_id == "dlrm-mlperf":
        n_int = cfg.n_sparse + 1
        inter = 2.0 * n_int * n_int * cfg.embed_dim
        d_inter = n_int * (n_int - 1) // 2
        return (mlp_flops((cfg.n_dense,) + cfg.bot_mlp)
                + inter
                + mlp_flops((cfg.bot_mlp[-1] + d_inter,) + cfg.top_mlp))
    if arch_id == "bst":
        S, D = cfg.seq_len + 1, cfg.embed_dim
        attn = cfg.n_blocks * (4 * 2.0 * S * D * D + 2 * 2.0 * S * S * D
                               + 2.0 * S * D * cfg.d_ff * 2)
        return attn + mlp_flops((S * D + cfg.n_other_feats,) + cfg.mlp_dims)
    if arch_id == "autoint":
        F = cfg.n_fields
        d_in, total = cfg.embed_dim, 0.0
        for _ in range(cfg.n_attn_layers):
            d_out = cfg.n_heads * cfg.d_attn
            total += 4 * 2.0 * F * d_in * d_out + 2 * 2.0 * F * F * d_out
            d_in = d_out
        return total + 2.0 * F * d_in
    if arch_id == "two-tower-retrieval":
        # retrieval path: item tower per candidate + dot
        return (mlp_flops((cfg.embed_dim,) + cfg.tower_mlp)
                + 2.0 * cfg.tower_mlp[-1])
    raise KeyError(arch_id)


def _smoke_data(arch_id: str, cfg, b: int):
    if arch_id == "dlrm-mlperf":
        return {k: jnp.asarray(v) for k, v in RD.dlrm_batch(b, cfg.n_dense, cfg.vocab_sizes).items()}
    if arch_id == "bst":
        return {k: jnp.asarray(v) for k, v in
                RD.bst_batch(b, cfg.seq_len, cfg.vocab_items, cfg.n_other_feats).items()}
    if arch_id == "autoint":
        return {k: jnp.asarray(v) for k, v in
                RD.autoint_batch(b, cfg.n_fields, cfg.vocab_per_field).items()}
    if arch_id == "two-tower-retrieval":
        return {k: jnp.asarray(v) for k, v in
                RD.twotower_batch(b, cfg.vocab_user, cfg.vocab_item, cfg.hist_len).items()}
    raise KeyError(arch_id)


# ---------------------------------------------------------------------------
# batch-spec builders (struct, specs) per model
# ---------------------------------------------------------------------------


def _dlrm_batch(cfg: R.DLRMConfig, b: int):
    struct = {
        "dense": jax.ShapeDtypeStruct((b, cfg.n_dense), jnp.float32),
        "sparse": jax.ShapeDtypeStruct((b, cfg.n_sparse), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b,), jnp.float32),
    }
    return struct, lambda r: {
        "dense": r.spec("batch", None),
        "sparse": r.spec("batch", None),
        "labels": r.spec("batch"),
    }


def _bst_batch(cfg: R.BSTConfig, b: int):
    struct = {
        "hist": jax.ShapeDtypeStruct((b, cfg.seq_len), jnp.int32),
        "target": jax.ShapeDtypeStruct((b,), jnp.int32),
        "other": jax.ShapeDtypeStruct((b, cfg.n_other_feats), jnp.float32),
        "labels": jax.ShapeDtypeStruct((b,), jnp.float32),
    }
    return struct, lambda r: {
        "hist": r.spec("batch", None),
        "target": r.spec("batch"),
        "other": r.spec("batch", None),
        "labels": r.spec("batch"),
    }


def _autoint_batch(cfg: R.AutoIntConfig, b: int):
    struct = {
        "sparse": jax.ShapeDtypeStruct((b, cfg.n_fields), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b,), jnp.float32),
    }
    return struct, lambda r: {
        "sparse": r.spec("batch", None),
        "labels": r.spec("batch"),
    }


def _twotower_batch(cfg: R.TwoTowerConfig, b: int):
    struct = {
        "user_id": jax.ShapeDtypeStruct((b,), jnp.int32),
        "hist": jax.ShapeDtypeStruct((b, cfg.hist_len), jnp.int32),
        "pos_item": jax.ShapeDtypeStruct((b,), jnp.int32),
        "logq": jax.ShapeDtypeStruct((b,), jnp.float32),
    }
    return struct, lambda r: {
        "user_id": r.spec("batch"),
        "hist": r.spec("batch", None),
        "pos_item": r.spec("batch"),
        "logq": r.spec("batch"),
    }


def _dlrm_shardings(cfg: R.DLRMConfig, rules: ShardingRules):
    return R.dlrm_shardings(cfg, rules)


def _bst_shardings(cfg: R.BSTConfig, rules: ShardingRules):
    p_struct = jax.eval_shape(lambda: R.bst_init(cfg, jax.random.key(0)))
    spec = jax.tree.map(lambda _: rules.spec(), p_struct)
    spec["item_table"] = rules.spec("table_rows", None)
    return spec


def _autoint_shardings(cfg: R.AutoIntConfig, rules: ShardingRules):
    p_struct = jax.eval_shape(lambda: R.autoint_init(cfg, jax.random.key(0)))
    spec = jax.tree.map(lambda _: rules.spec(), p_struct)
    spec["table"] = rules.spec("table_rows", None)
    return spec


def _twotower_shardings(cfg: R.TwoTowerConfig, rules: ShardingRules):
    p_struct = jax.eval_shape(lambda: R.twotower_init(cfg, jax.random.key(0)))
    spec = jax.tree.map(lambda _: rules.spec(), p_struct)
    spec["user_table"] = rules.spec("table_rows", None)
    spec["item_table"] = rules.spec("table_rows", None)
    return spec


# ---------------------------------------------------------------------------
# The four archs (published configs)
# ---------------------------------------------------------------------------

_dlrm_cfg = R.DLRMConfig(
    name="dlrm-mlperf", n_dense=13, embed_dim=128,
    vocab_sizes=CRITEO_1TB_VOCAB_SIZES,
    bot_mlp=(512, 256, 128), top_mlp=(1024, 1024, 512, 256, 1),
)
_dlrm_smoke = dataclasses.replace(
    _dlrm_cfg, name="dlrm-smoke",
    vocab_sizes=tuple(min(v, 50) for v in CRITEO_1TB_VOCAB_SIZES),
    bot_mlp=(32, 16), top_mlp=(32, 16, 1), embed_dim=16,
)

_bst_cfg = R.BSTConfig(
    name="bst", vocab_items=2_097_152, embed_dim=32, seq_len=20,
    n_blocks=1, n_heads=8, d_ff=128, mlp_dims=(1024, 512, 256, 1),
)
_bst_smoke = dataclasses.replace(
    _bst_cfg, name="bst-smoke", vocab_items=500, seq_len=8,
    mlp_dims=(32, 16, 1), d_ff=32,
)

_autoint_cfg = R.AutoIntConfig(
    name="autoint", n_fields=39, vocab_per_field=131_072, embed_dim=16,
    n_attn_layers=3, n_heads=2, d_attn=32,
)
_autoint_smoke = dataclasses.replace(
    _autoint_cfg, name="autoint-smoke", n_fields=8, vocab_per_field=50,
)

_twotower_cfg = R.TwoTowerConfig(
    name="two-tower-retrieval", vocab_user=4_194_304, vocab_item=8_388_608,
    hist_len=20, embed_dim=256, tower_mlp=(1024, 512, 256),
)
_twotower_smoke = dataclasses.replace(
    _twotower_cfg, name="twotower-smoke", vocab_user=300, vocab_item=500,
    hist_len=8, embed_dim=32, tower_mlp=(64, 32),
)

RECSYS_ARCHS = [
    RecsysArch("dlrm-mlperf", "arXiv:1906.00091; MLPerf Criteo 1TB",
               _dlrm_cfg, R.dlrm_init, R.dlrm_loss, R.dlrm_forward,
               _dlrm_batch, _dlrm_shardings, _dlrm_smoke),
    RecsysArch("bst", "arXiv:1905.06874 (Alibaba)",
               _bst_cfg, R.bst_init, R.bst_loss, R.bst_forward,
               _bst_batch, _bst_shardings, _bst_smoke),
    RecsysArch("autoint", "arXiv:1810.11921",
               _autoint_cfg, R.autoint_init, R.autoint_loss, R.autoint_forward,
               _autoint_batch, _autoint_shardings, _autoint_smoke),
    RecsysArch("two-tower-retrieval", "Yi et al. RecSys'19 (YouTube)",
               _twotower_cfg, R.twotower_init, R.twotower_loss,
               lambda p, b, c, r: R.user_tower(p, b, c, r),
               _twotower_batch, _twotower_shardings, _twotower_smoke),
]
