"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts — unsupported
primitives inside a kernel, scalar stores to VMEM, blocks that break the
(8, 128) tiling rule — so every kernel on the served path, and the jitted
select graph, is compiled here at the paper's corpus sizes.  The topology
is described inside a module fixture (never at import), so each test
worker collects the same tests and only the worker that runs this file
loads the TPU library; where it cannot be described the tests skip.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.backends import JitJaxBackend, PlanStructure
from repro.kernels.mmr.ops import mmr_select
from repro.kernels.pem_score.ops import pem_score

D = 128
V5E_HBM = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM, used


@pytest.mark.parametrize("n", [240_000, 1_000_448])
@pytest.mark.parametrize("b", [1, 128])
def test_pem_score_compiles_for_v5e(one_chip, n, b):
    s = _spec
    compiled = pem_score.lower(
        s(one_chip, (n, D)), s(one_chip, (D, b)), s(one_chip, (D, b)),
        s(one_chip, (n,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("k", [10, 500])
@pytest.mark.parametrize("b", [1, 4])
def test_mmr_select_compiles_for_v5e(one_chip, k, b):
    pool = 2048
    compiled = mmr_select.lower(
        _spec(one_chip, (b, pool, D)), _spec(one_chip, (b, pool)),
        k, 0.7).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_rows", [262_144, 1_048_576])
def test_jit_jax_select_graph_compiles_for_v5e(one_chip, n_rows):
    """The composed diverse query's graph: decay + suppress, pow2 top-k
    width 2048 (the 1500-row MMR pool) and the 512-step MMR tail."""
    b = 1
    structure = PlanStructure(batch=b, n_rows=n_rows, has_decay=True,
                              suppress_bucket=1, width=2048, mmr_k=512)
    fn = JitJaxBackend()._build_select(structure)
    s = _spec
    compiled = fn.lower(
        s(one_chip, (n_rows, D)), s(one_chip, (D, b)), s(one_chip, (D, b)),
        s(one_chip, (n_rows,)), s(one_chip, (b,)),
        s(one_chip, (n_rows,), jnp.bool_), s(one_chip, (b,)),
        s(one_chip, (b,), jnp.int32), s(one_chip, (1, 1))).compile()
    _fits_one_chip(compiled)


def test_sharded_select_graph_compiles_for_four_v5e_chips(topo):
    """The passage deployment's fullest graph on a 2x2 mesh: 8,841,823
    768-d rows on their row grid (8,912,896, a quarter a chip), a cohort of
    32 with a suppress direction, the 2,048-wide pool of a diverse search
    and its MMR tail.  Each chip holds a quarter of the rows, and the whole
    program fits one chip's memory."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.backends import ShardedBackend, _row_grid

    rows, d, b = _row_grid(8_841_823), 768, 32
    backend = ShardedBackend()
    backend._shards_mesh = Mesh(topo.devices, ("shards",))
    structure = PlanStructure(batch=b, n_rows=rows, has_decay=False,
                              suppress_bucket=1, width=2048, mmr_k=16)
    fn = backend._build_select(structure)

    def s(shape, spec, dtype=jnp.float32):
        return _spec(NamedSharding(backend._shards_mesh, spec), shape, dtype)

    compiled = fn.lower(
        s((rows, d), P("shards", None)), s((d, b), P()), s((d, b), P()),
        s((rows,), P("shards")), s((b,), P()), s((rows,), P("shards"), jnp.bool_),
        s((b,), P()), s((b,), P(), jnp.int32), s((1, 1), P())).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= rows // 4 * d * 4
    _fits_one_chip(compiled)
    assert "all-gather" in compiled.as_text()
