"""MMR diverse-selection Pallas kernel (TPU target).

`diverse` is the paper's only modulation with data-dependent control flow:
k iterations of (argmax over pool) -> (rank-1 similarity update). Pool sizes
are small (3x oversample of K=500 -> n <= 4096), so the WHOLE pool lives in
VMEM and the loop never touches HBM:

* pool embeddings tile  (n x d)  : <= 4096 x 128 x 4B = 2MB VMEM
* the selected row e[j] is extracted MXU-style with a one-hot matmul
  (onehot(j) @ E), avoiding dynamic gather which TPUs dislike;
* similarity update  e[j] @ E^T  is a (1 x d)x(d x n) matmul on the MXU;
* running state (max_sim, taken) and the (1, k) index/value rows ride the
  loop carry as vectors: Mosaic cannot store a scalar to VMEM, so each
  output row is built with ``where(iota == i, ...)`` and stored ONCE; and
  it cannot carry a bool vector through a loop, so ``taken`` is 0/1 f32.

Layout: ``rel`` and both outputs carry a unit middle axis, (B, 1, n) and
(B, 1, k), so every block's last two dims equal the array's — the (8, 128)
tiling rule holds for any batch B.  All dots run at full f32 precision
(``HIGHEST`` lowers to Mosaic's fp32 contract precision): the one-hot
extraction is then exact and the similarities match the f32 host oracle.

Grid: one program per query (fully parallel across the serving batch).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import check_interpret

NEG = -1e30
_HI = jax.lax.Precision.HIGHEST


def _mmr_kernel(e_ref, rel_ref, idx_out, val_out, *, k: int, lam: float):
    e = e_ref[0].astype(jnp.float32)          # (n, d)
    rel = rel_ref[0].astype(jnp.float32)      # (1, n)
    n = rel.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    kiota = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    invalid = rel <= NEG * 0.5                # NEG-padded slots

    def body(i, carry):
        max_sim, taken, idx_row, val_row = carry  # taken: 0/1 f32
        penalty = jnp.where(max_sim <= NEG * 0.5, 0.0, max_sim)
        mmr = lam * rel - (1.0 - lam) * penalty
        # padding must stay NEG even at lam=0, where lam*rel zeroes the
        # sentinel and -penalty alone would leave padded slots finite
        mmr = jnp.where(jnp.logical_or(taken > 0.0, invalid), NEG, mmr)
        best = jnp.max(mmr)
        # first occurrence of the max: the host oracle's tie rule
        j = jnp.min(jnp.where(mmr == best, iota, n))
        chosen = iota == j                    # (1, n) one-hot row mask
        # e[j] without dynamic gather: onehot(j) @ E -> (1, d) on the MXU.
        ej = jnp.dot(chosen.astype(jnp.float32), e, precision=_HI,
                     preferred_element_type=jnp.float32)
        sim_j = jax.lax.dot_general(            # e[j] @ E^T -> (1, n)
            ej, e, (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)
        max_sim = jnp.maximum(max_sim, sim_j)
        taken = jnp.where(chosen, 1.0, taken)
        idx_row = jnp.where(kiota == i, j, idx_row)
        val_row = jnp.where(kiota == i, best, val_row)
        return max_sim, taken, idx_row, val_row

    init = (jnp.full((1, n), NEG, jnp.float32), jnp.zeros((1, n), jnp.float32),
            jnp.zeros((1, k), jnp.int32), jnp.zeros((1, k), jnp.float32))
    _, _, idx_row, val_row = jax.lax.fori_loop(0, k, body, init)
    idx_out[0] = idx_row
    val_out[0] = val_row


@functools.partial(jax.jit, static_argnames=("k", "lam", "interpret"))
def mmr_pallas(
    embeds: jnp.ndarray,  # (B, n, d)
    rel: jnp.ndarray,     # (B, n)
    k: int,
    lam: float = 0.7,
    *,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, n, d = embeds.shape
    kern = functools.partial(_mmr_kernel, k=k, lam=lam)
    idx, val = pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, n, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=check_interpret(interpret),
        name="mmr_select",
    )(embeds, rel[:, None, :])
    return idx[:, 0, :], val[:, 0, :]
