"""Pallas TPU kernels for squared-L2 distance (paper: L2SqrDistance).

Two forms:

* `l2sq_rowwise` — paper-faithful VPU kernel: one query against many
  reference rows, fused subtract + multiply-accumulate (the RVV
  vfsub/vfmacc/vfredsum loop), tiled over (refs, feature-chunks) with the
  feature axis as a serial reduction.

* `l2sq_matrix` — beyond-paper MXU kernel for the KNN use case: the full
  pairwise matrix via ||a||^2 + ||b||^2 - 2 a.b^T, with the cross term on
  the systolic array and the (precomputed) norms added at the last
  K-block.  The paper computes distances row-by-row; a matrix engine makes
  the batched form compute-bound instead of load-bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning


# --------------------------------------------------------------------------
# Row-wise (paper-faithful) form
# --------------------------------------------------------------------------
def _l2_rowwise_kernel(q_ref, refs_ref, out_ref):
    k_blk = pl.program_id(1)
    q = q_ref[...]                    # (1, bk)
    refs = refs_ref[...]              # (bn, bk)
    d = refs - q                      # broadcast over rows (vfsub)
    partial = jnp.sum(d * d, axis=1, keepdims=True)   # (bn, 1)  (vfmacc+reduce)

    @pl.when(k_blk == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(k_blk != 0)
    def _accum():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def l2sq_rowwise(q: jax.Array, refs: jax.Array, *, block_n: int = 256,
                 block_k: int = 128, interpret: bool = False) -> jax.Array:
    """out[n] = ||refs[n] - q||^2  -> (N,) float32.  Pre-padded N, K."""
    N, K = refs.shape
    grid = (N // block_n, K // block_k)
    out = pl.pallas_call(
        _l2_rowwise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_k), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, block_k), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        compiler_params=tuning.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="l2sq_rowwise",
    )(q.reshape(1, K), refs)
    return out[:, 0]


# --------------------------------------------------------------------------
# Matrix (beyond-paper, MXU) form
# --------------------------------------------------------------------------
def _l2_matrix_kernel(a_ref, b_ref, asq_ref, bsq_ref, out_ref, *,
                      k_blocks: int):
    k_blk = pl.program_id(2)
    a = a_ref[...]                    # (bm, bk)
    b = b_ref[...]                    # (bn, bk)
    cross = jax.lax.dot(a, b.T, preferred_element_type=jnp.float32)

    @pl.when(k_blk == 0)
    def _init():
        out_ref[...] = -2.0 * cross

    @pl.when(k_blk != 0)
    def _accum():
        out_ref[...] += -2.0 * cross

    @pl.when(k_blk == k_blocks - 1)
    def _final():
        out_ref[...] = jnp.maximum(
            out_ref[...] + asq_ref[...] + bsq_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def l2sq_matrix(a: jax.Array, b: jax.Array, *, block_m: int = 128,
                block_n: int = 128, block_k: int = 128,
                interpret: bool = False) -> jax.Array:
    """out[m, n] = ||a[m] - b[n]||^2  -> (M, N) float32.  Pre-padded M/N/K."""
    M, K = a.shape
    N, _ = b.shape
    a_sq = jnp.sum(a * a, axis=1, keepdims=True)          # (M, 1)
    b_sq = jnp.sum(b * b, axis=1, keepdims=True).T        # (1, N)
    k_blocks = K // block_k
    grid = (M // block_m, N // block_n, k_blocks)
    return pl.pallas_call(
        functools.partial(_l2_matrix_kernel, k_blocks=k_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=tuning.compiler_params("parallel", "parallel",
                                               "arbitrary"),
        interpret=interpret,
        name="l2sq_matrix",
    )(a, b, a_sq, b_sq)
