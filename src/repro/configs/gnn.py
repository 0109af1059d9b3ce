"""PNA architecture cells [arXiv:2004.05718].

Shapes (assignment):
    full_graph_sm  n=2,708  e=10,556   d_feat=1,433 (Cora-scale, full batch)
    minibatch_lg   n=232,965 e=114.6M  seeds=1,024 fanout 15-10 (Reddit-scale,
                   REAL neighbor sampler -> padded subgraph, static shapes)
    ogb_products   n=2,449,029 e=61.9M d_feat=100 (full-batch-large)
    molecule       30 nodes / 64 edges x batch 128 (graph-level task)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ArchSpec, LoweredSpec, ShapeCell, with_sharding
from repro.data.graph import (
    CSRGraph,
    _max_edges,
    _max_nodes,
    make_graph,
    make_molecule_batch,
    sample_subgraph,
)
from repro.dist.sharding import ShardingRules, default_rules
from repro.launch.mesh import make_local_mesh
from repro.models import pna
from repro.train.optimizer import AdamWConfig, OptState, adamw_update, init_opt_state
import numpy as np

GNN_SHAPES: Dict[str, Dict[str, Any]] = {
    "full_graph_sm": dict(kind="train", n=2708, e=10556, d_feat=1433,
                          n_classes=7, task="node"),
    "minibatch_lg": dict(kind="train", seeds=1024, fanouts=(15, 10), d_feat=602,
                         n_classes=41, task="node"),
    "ogb_products": dict(kind="train", n=2_449_029, e=61_859_140, d_feat=100,
                         n_classes=47, task="node"),
    "molecule": dict(kind="train", batch=128, nodes=30, edges=64, d_feat=28,
                     n_classes=2, task="graph"),
}


def _round512(x: int) -> int:
    """Pad node/edge budgets to a 512 multiple so they shard evenly on any
    production mesh axis combination. Padding is masked (sink node), exactly
    as the data pipeline pads sampled subgraphs (data/graph.py)."""
    return (x + 511) // 512 * 512


def _shape_dims(s: Dict[str, Any]):
    if "seeds" in s:
        n = _max_nodes(s["seeds"], s["fanouts"]) + 1
        e = _max_edges(s["seeds"], s["fanouts"])
    elif "batch" in s:
        n, e = s["batch"] * s["nodes"], s["batch"] * s["edges"]
    else:
        n, e = s["n"], s["e"]
    return _round512(n), _round512(e)


class PNAArch(ArchSpec):
    family = "gnn"

    def __init__(self):
        self.arch_id = "pna"
        self.source = "arXiv:2004.05718; paper"
        self.n_layers = 4
        self.d_hidden = 75

    def cells(self) -> Dict[str, ShapeCell]:
        out = {}
        for name, s in GNN_SHAPES.items():
            n, e = _shape_dims(s)
            out[name] = ShapeCell(name=name, kind="train",
                                  desc=f"nodes={n} edges={e} d_feat={s['d_feat']}")
        return out

    def model_flops(self, shape: str) -> float:
        s = GNN_SHAPES[shape]
        n, e = _shape_dims(s)
        d = self.d_hidden
        per_layer = 2.0 * e * (2 * d) * d + 2.0 * n * (13 * d) * d
        fwd = (2.0 * n * s["d_feat"] * d
               + self.n_layers * per_layer
               + 2.0 * n * d * s["n_classes"])
        return 3.0 * fwd  # train step (fwd + bwd)

    def _cfg(self, s: Dict[str, Any]) -> pna.PNAConfig:
        return pna.PNAConfig(
            name="pna", n_layers=self.n_layers, d_hidden=self.d_hidden,
            d_feat=s["d_feat"], n_classes=s["n_classes"], task=s["task"],
            n_graphs=s.get("batch", 1),
        )

    def build(self, shape: str, mesh: Mesh, rules: ShardingRules) -> LoweredSpec:
        s = GNN_SHAPES[shape]
        cfg = self._cfg(s)
        n, e = _shape_dims(s)
        p_struct = jax.eval_shape(lambda: pna.init_params(cfg, jax.random.key(0)))
        p_spec = jax.tree.map(lambda _: rules.spec(), p_struct)  # tiny: replicate
        params = with_sharding(p_struct, p_spec, mesh)
        o_struct = jax.eval_shape(init_opt_state, p_struct)
        opt = with_sharding(
            o_struct,
            OptState(step=rules.spec(), m=p_spec, v=jax.tree.map(lambda x: x, p_spec)),
            mesh,
        )
        batch = {
            "feats": jax.ShapeDtypeStruct((n, s["d_feat"]), jnp.float32),
            "edge_src": jax.ShapeDtypeStruct((e,), jnp.int32),
            "edge_dst": jax.ShapeDtypeStruct((e,), jnp.int32),
            "labels": jax.ShapeDtypeStruct(
                (s.get("batch", n) if s["task"] == "graph" else n,), jnp.int32),
            "node_mask": jax.ShapeDtypeStruct((n,), jnp.bool_),
            "edge_mask": jax.ShapeDtypeStruct((e,), jnp.bool_),
        }
        bspec = {
            "feats": rules.spec("nodes", None),
            "edge_src": rules.spec("edges"),
            "edge_dst": rules.spec("edges"),
            "labels": rules.spec("nodes" if s["task"] == "node" else None),
            "node_mask": rules.spec("nodes"),
            "edge_mask": rules.spec("edges"),
        }
        if s["task"] == "graph":
            batch["graph_ids"] = jax.ShapeDtypeStruct((n,), jnp.int32)
            bspec["graph_ids"] = rules.spec("nodes")
        batch = with_sharding(batch, bspec, mesh)
        ocfg = AdamWConfig()

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(pna.loss_fn)(params, batch, cfg, rules)
            params, opt_state, metrics = adamw_update(ocfg, params, grads, opt_state)
            return params, opt_state, {"loss": loss, **metrics}

        return LoweredSpec(fn=train_step, args=(params, opt, batch),
                           donate_argnums=(0, 1),
                           static_desc=f"pna/{shape}")

    def smoke_run(self) -> Dict[str, Any]:
        mesh = make_local_mesh()
        rules = default_rules(mesh)
        out: Dict[str, Any] = {}
        with mesh:
            # node task on a small graph THROUGH the real sampler
            g = make_graph(400, 1600, 24, n_classes=5, seed=0)
            csr = CSRGraph(400, g.edge_src, g.edge_dst)
            sub = sample_subgraph(g, csr, np.arange(32), [4, 3],
                                  np.random.default_rng(0))
            cfg = pna.PNAConfig(name="pna-smoke", n_layers=2, d_hidden=16,
                                d_feat=24, n_classes=5)
            params = pna.init_params(cfg, jax.random.key(0))
            batch = {
                "feats": jnp.asarray(sub.feats),
                "edge_src": jnp.asarray(sub.edge_src),
                "edge_dst": jnp.asarray(sub.edge_dst),
                "labels": jnp.asarray(sub.labels),
                "node_mask": jnp.asarray(sub.node_mask),
                "edge_mask": jnp.asarray(sub.edge_mask),
            }
            loss, grads = jax.value_and_grad(pna.loss_fn)(params, batch, cfg, rules)
            out["loss"] = float(loss)
            out["grad_finite"] = all(
                bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(grads))
            # graph task
            mol = make_molecule_batch(8, 10, 20, 24, n_classes=5, seed=1)
            cfg_g = dataclasses.replace(cfg, task="graph", n_graphs=8)
            mb = {
                "feats": jnp.asarray(mol.feats),
                "edge_src": jnp.asarray(mol.edge_src),
                "edge_dst": jnp.asarray(mol.edge_dst),
                "labels": jnp.asarray(mol.labels),
                "node_mask": jnp.asarray(mol.node_mask),
                "edge_mask": jnp.asarray(mol.edge_mask),
                "graph_ids": jnp.asarray(mol.graph_ids),
            }
            logits = pna.forward(params, mb, cfg_g, rules)
            out["graph_logits_shape"] = tuple(logits.shape)
            out["graph_loss"] = float(pna.loss_fn(params, mb, cfg_g, rules))
        return out


GNN_ARCHS = [PNAArch()]
