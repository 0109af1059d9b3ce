"""Fused PEM scoring Pallas kernel (TPU target, interpret-validated on CPU).

One pass over the corpus matrix computes modulated scores for a whole batch
of queries:

    out[n, b] = decay[n] * (M[n, :] . Qpre[:, b]) + M[n, :] . Qsup[:, b]

TPU mapping (DESIGN.md §2.1):
* corpus tile (BLOCK_N x d) streams HBM->VMEM exactly once per query block —
  vs the paper's numpy engine which re-reads M for every direction;
* d = 128 Matryoshka dims align exactly with MXU lanes; both matmuls hit the
  MXU at full f32 precision (``HIGHEST`` lowers to Mosaic's fp32 contract
  precision, so scores match the f32 host oracle) with fp32 accumulation;
* decay multiply + sum is a VPU epilogue fused in-register;
* grid is fully parallel (no cross-block state).

VMEM budget at defaults (BLOCK_N=1024, d=128, BLOCK_B=128, bf16 matrix):
M tile 256KB + Q tiles 128KB + out tile 512KB + decay 4KB << 16MB VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import check_interpret

BLOCK_N = 1024   # corpus rows per tile (multiple of 8 sublanes)
BLOCK_B = 128    # query columns per tile (multiple of 128 lanes)
_HI = jax.lax.Precision.HIGHEST


def _pem_score_kernel(m_ref, qpre_ref, qsup_ref, decay_ref, out_ref):
    m = m_ref[...].astype(jnp.float32)                       # (bn, d)
    pre = jnp.dot(m, qpre_ref[...].astype(jnp.float32), precision=_HI,
                  preferred_element_type=jnp.float32)        # (bn, bq) MXU
    sup = jnp.dot(m, qsup_ref[...].astype(jnp.float32), precision=_HI,
                  preferred_element_type=jnp.float32)        # (bn, bq) MXU
    out_ref[...] = decay_ref[...] * pre + sup                # VPU epilogue


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_b", "interpret")
)
def pem_score_pallas(
    matrix: jnp.ndarray,   # (N, d), N % block_n == 0
    q_pre: jnp.ndarray,    # (d, B), B % block_b == 0
    q_sup: jnp.ndarray,    # (d, B)
    decay: jnp.ndarray,    # (N,)
    *,
    block_n: int = BLOCK_N,
    block_b: int = BLOCK_B,
    interpret: bool = False,
) -> jnp.ndarray:
    n, d = matrix.shape
    b = q_pre.shape[1]
    assert n % block_n == 0 and b % block_b == 0, (n, b, block_n, block_b)
    grid = (n // block_n, b // block_b)
    return pl.pallas_call(
        _pem_score_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_b), lambda i, j: (0, j)),
            pl.BlockSpec((d, block_b), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_b), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=check_interpret(interpret),
        name="pem_score",
    )(matrix, q_pre, q_sup, decay.reshape(n, 1).astype(jnp.float32))
