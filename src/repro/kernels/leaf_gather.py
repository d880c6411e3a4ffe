"""Pallas TPU kernel for leaf-value accumulation
(paper: CalculateLeafValues / CalculateLeafValuesMulti).

This is the hotspot the paper explicitly could NOT vectorize: RVV 0.7.1
gather/scatter is too slow to pay for the few arithmetic ops per element
(their Tables 2-3 show speedup 0.98-1.03x).  The TPU answer is to avoid
the gather unit entirely: `sum_t leaf_values[t, :, idx[n, t]]` becomes a
one-hot matmul `leaf_values[group] @ onehot(idx[:, group])` on the
128x128 MXU, G trees at a time.  The indirect addressing turns into
dense systolic compute.

The index arrives tree-major, (T, N) — the layout `leaf_index` writes —
so its blocks are lane-dense for any tree block that is a multiple of 8.
The leaf table arrives packed class-major, (T/G, Cp, G*Lp), with Cp a
multiple of 8 and Lp the leaf count rounded up to 8 (`ops.class_major`
builds it once): row q holds trees qG .. qG+G-1 side by side on lanes,
tree g of the group in lanes [g*Lp, g*Lp + L).  G comes from the leaf
count alone (`tuning.trees_per_step`), so every tree's table is whole
(8, 128) tiles of the group's row, which is also the layout the device
stores the array in: XLA inserts no relayout before the call, and one
contraction pays the per-step cost for G trees.

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
    packed class-major (bt/G, Cp, G*Lp) leaf-value block; G is read off
    the two shapes.  One group of G trees per loop step: each tree's
    index row against a sublane iota is its (Lp, bn) one-hot, the G
    one-hots stack on sublanes into the group's (G*Lp, bn) one-hot, and
    the group's (Cp, G*Lp) table times it on the MXU is the G trees'
    (Cp, bn) contribution.  The one-hot is exact, and the contraction
    runs at HIGHEST precision so the sum carries full float32 leaf
    values.  Samples stay on lanes throughout, so the sum comes out
    (Cp, bn); `kernels.ops` transposes the (Cp, N) result and drops the
    padded classes.  Shared by `leaf_gather` and the fused kernels'
    stage 3."""
    bt, bn = idx_t_ref.shape
    bq, C, W = lv_ref.shape
    G = bt // bq
    leaf_iota = jax.lax.broadcasted_iota(jnp.int32, (W // G, bn), 0)

    def body(q, acc):
        onehot = jnp.concatenate(
            [(leaf_iota == idx_t_ref[pl.ds(q * G + g, 1), :]).astype(
                jnp.float32) for g in range(G)], axis=0)   # (G*Lp, bn)
        return acc + jax.lax.dot_general(
            lv_ref[q], onehot, (((1,), (0,)), ((), ())),
            precision=LEAF_PRECISION, preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, bq, body, jnp.zeros((C, bn), jnp.float32))


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


def trees_per_row(n_trees: int, leaf_values: jax.Array) -> int:
    """G of a packed (n_trees/G, Cp, G*Lp) table summing n_trees trees."""
    g = n_trees // max(leaf_values.shape[0], 1)
    if g < 1 or g * leaf_values.shape[0] != n_trees:
        raise ValueError(
            f"a packed leaf table of {leaf_values.shape[0]} rows cannot hold "
            f"{n_trees} trees in whole groups (use ops.class_major)")
    return g


@functools.partial(jax.jit, static_argnames=("block_n", "block_t", "interpret"))
def leaf_gather(idx_t: jax.Array, leaf_values: jax.Array, *,
                block_n: int = 128, block_t: int = 16,
                interpret: bool = False) -> jax.Array:
    """pred^T[c, n] = sum_t lv[t, c, idx_t[t, n]] -> (Cp, N) f32.

    `idx_t` is the tree-major (T, N) index `leaf_index` writes and
    `leaf_values` the packed class-major (T/G, Cp, G*Lp) table
    `ops.class_major` builds; the sum comes out class-major too
    (samples on lanes).
    Pre-padded: N % block_n == 0 (block_n a multiple of 128), T %
    block_t == 0 with block_t a multiple of G, Cp a multiple of 8.
    Padded trees, leaf slots and classes must have all-zero leaf values.
    """
    T, N = idx_t.shape
    _, C, W = leaf_values.shape
    G = trees_per_row(T, leaf_values)
    if N % block_n or T % block_t or block_t % G:
        raise ValueError(
            f"leaf_gather requires padded inputs: N={N} % block_n="
            f"{block_n}, T={T} % block_t={block_t} and block_t % G={G} "
            "must be 0 (use kernels.ops for automatic padding)")
    return pl.pallas_call(
        _leaf_gather_kernel,
        grid=(N // block_n, T // block_t),
        in_specs=[
            pl.BlockSpec((block_t, block_n), lambda i, j: (j, i)),
            pl.BlockSpec((block_t // G, C, W), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((C, block_n), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, N), jnp.float32),
        compiler_params=tuning.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="leaf_gather",
    )(idx_t, leaf_values)
