"""Pallas TPU kernel for leaf-value accumulation
(paper: CalculateLeafValues / CalculateLeafValuesMulti).

This is the hotspot the paper explicitly could NOT vectorize: RVV 0.7.1
gather/scatter is too slow to pay for the few arithmetic ops per element
(their Tables 2-3 show speedup 0.98-1.03x).  The TPU answer is to avoid
the gather unit entirely: `sum_t leaf_values[t, :, idx[n, t]]` becomes a
one-hot matmul `leaf_values[t] @ onehot(idx[:, t])` on the 128x128
MXU, one tree at a time.  The indirect addressing turns into dense systolic
compute.

The index arrives tree-major, (T, N) — the layout `leaf_index` writes —
so its blocks are lane-dense for any tree block that is a multiple of 8.
The leaf table arrives class-major, (T, Cp, L) with Cp a multiple of 8
(`layout.lower` builds it once): each tree's (Cp, L) table is whole
(8, 128) tiles with the leaves on lanes, which is also the layout the
device stores the array in, so XLA inserts no relayout before the call.

Grid: (N / block_n, T / block_t) with the T axis as a serial reduction;
the output tile is initialized at t-block 0 and accumulated in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning

# Leaf values are arbitrary float32: the one-hot contraction must not
# round them to bfloat16 on the MXU.
LEAF_PRECISION = jax.lax.Precision.HIGHEST


def accumulate_leaves(idx_t_ref, lv_ref) -> jax.Array:
    """sum_t lv[t, :, idx_t[t, :]] over one tree block -> (Cp, bn) f32.

    `idx_t_ref` is the tree-major (bt, bn) index block, `lv_ref` the
    class-major (bt, Cp, L) leaf-value block.  One tree per loop step:
    its index row against a sublane iota is the (L, bn) one-hot, and
    that tree's (Cp, L) table times it on the MXU is the tree's (Cp, bn)
    contribution.  The one-hot is exact, and the contraction runs at
    HIGHEST precision so the sum carries full float32 leaf values.
    Samples stay on lanes throughout, so the sum comes out (Cp, bn);
    `kernels.ops` transposes the (Cp, N) result and drops the padded
    classes.  Shared by `leaf_gather` and the fused kernels' stage 3."""
    bt, bn = idx_t_ref.shape
    _, C, L = lv_ref.shape
    leaf_iota = jax.lax.broadcasted_iota(jnp.int32, (L, bn), 0)

    def body(t, acc):
        onehot = (leaf_iota == idx_t_ref[pl.ds(t, 1), :]).astype(
            jnp.float32)                                     # (L, bn)
        return acc + jax.lax.dot_general(
            lv_ref[t], onehot, (((1,), (0,)), ((), ())),
            precision=LEAF_PRECISION, preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, bt, body, jnp.zeros((C, bn), jnp.float32))


def accumulate_into(out_ref, acc: jax.Array) -> None:
    """out = acc at the first tree block, out += acc after (the grid's
    tree axis is the serial reduction)."""
    t_blk = pl.program_id(1)

    @pl.when(t_blk == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(t_blk != 0)
    def _accum():
        out_ref[...] += acc


def _leaf_gather_kernel(idx_ref, lv_ref, out_ref):
    accumulate_into(out_ref, accumulate_leaves(idx_ref, lv_ref))


@functools.partial(jax.jit, static_argnames=("block_n", "block_t", "interpret"))
def leaf_gather(idx_t: jax.Array, leaf_values: jax.Array, *,
                block_n: int = 128, block_t: int = 16,
                interpret: bool = False) -> jax.Array:
    """pred^T[c, n] = sum_t leaf_values[t, c, idx_t[t, n]] -> (Cp, N) f32.

    `idx_t` is the tree-major (T, N) index `leaf_index` writes and
    `leaf_values` the class-major (T, Cp, L) table; the sum comes out
    class-major too (samples on lanes).
    Pre-padded: N % block_n == 0 (block_n a multiple of 128), T %
    block_t == 0, Cp a multiple of 8.  Padded trees and classes must
    have all-zero leaf_values.
    """
    T, N = idx_t.shape
    _, C, L = leaf_values.shape
    if N % block_n or T % block_t:
        raise ValueError(
            f"leaf_gather requires padded inputs: N={N} % block_n="
            f"{block_n} and T={T} % block_t={block_t} must be 0 "
            "(use kernels.ops for automatic padding)")
    return pl.pallas_call(
        _leaf_gather_kernel,
        grid=(N // block_n, T // block_t),
        in_specs=[
            pl.BlockSpec((block_t, block_n), lambda i, j: (j, i)),
            pl.BlockSpec((block_t, C, L), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((C, block_n), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, N), jnp.float32),
        compiler_params=tuning.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="leaf_gather",
    )(idx_t, leaf_values)
