"""Pallas TPU kernel for the leaf index of non-symmetric trees
(CatBoost's Lossguide and Depthwise grow policies).

A node of such a tree tests its own feature and border, so each row
reaches its leaf by its own path, and no per-depth split exists to
compare against.  The kernel tests every node and then matches whole
paths, all of it dense work on the MXU:

  gathered bins  G[t, j, n] = bins[n, nf[t, j]]: onehot(nf) (bt*W, F)
                 against the bins panel, as `leaf_index` gathers
  signs          s[t, j, n] = +1 if G >= nb[t, j] (the row goes right
                 at node j), else -1
  scores         score[t, l, n] = sum_j P[t, l, j] s[t, j, n], where the
                 path matrix P holds +1 (right) / -1 (left) for the
                 nodes on the path to leaf l and 0 elsewhere
  leaf           idx[t, n] = sum_l l [score[t, l, n] == plen[t, l]]

score equals the path length plen only where the row agrees with every
step of the path, and a row agrees with exactly one leaf's path: the
one the walk from the root takes.  Every operand is a small integer
(bin ids <= 255 and onehots in bfloat16, signs and path entries in
{-1, 0, +1}, sums of at most W terms, accumulated in float32), so the
index is exact: bit for bit the traversal's (`ref.node_index`).
Padded leaves carry plen = -1, which no score of an all-zero path row
reaches, and padded trees have no leaf that matches, so they land in
leaf 0.

Layout: W = Np = Lp node and leaf slots per tree, a power of two
<= 128, and k = 128 / W trees share each 128-lane row of the node
arrays (`layout.NodesLayout`):

  node_features, node_bins, path_len   (Tp / k, 128) int32
  paths                                (Tp / k, W, 128) bfloat16

so every array is lane-dense and stored the way the kernel reads it.
The path match runs per group of k trees as one (128, 128) block-
diagonal matrix, built in VMEM from the group's (W, 128) rows, times
the group's (128, bn) signs: a full MXU tile.  The index comes out
tree-major, (T, N), as `leaf_index` writes it for `leaf_gather`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning
from repro.kernels.leaf_index import gather_operand

LANE = tuning.LANE
# Node rows (trees x W) one grid step tests: 1024 / W trees a step,
# a whole (8, 128) tile of each packed node array.  On one v5e, 32 and
# 64 trees a step at W = 32 take the same time (1.395 and 1.391 ms a
# 256-row chunk of 10,048 trees), 128 trees a step 6% longer.
NODE_ROWS = 1024
# plen of a padded leaf: its path row is all zero, so its score is 0.
PAD_PATH_LEN = -1


def tree_block(width: int) -> int:
    """Trees per grid step at W node slots: a whole number of 8-row
    tiles of the packed node arrays (k = 128 / W trees a row)."""
    return NODE_ROWS // width


def _column(packed: jax.Array, rows: int) -> jax.Array:
    """(q, 128) packed rows -> the (rows, 1) column whose row r holds
    packed[r // 128, r % 128]: node j of tree t at row t * W + j.

    A lane -> sublane move as MXU work: a one-hot row selector (rows,
    q) times the packed block spreads each row across its 128 lanes,
    and a lane mask keeps lane r % 128.  The values are integers below
    2^24 (feature ids, bin ids, path lengths) and the padded bins'
    2^30; HIGHEST precision carries each exactly."""
    q = packed.shape[0]
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, q), 0)
    q_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, q), 1)
    select = ((r_iota >> 7) == q_iota).astype(jnp.float32)
    spread = jax.lax.dot_general(
        select, packed.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                 # (rows, 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 0) & (LANE - 1)
    return jnp.sum(jnp.where(lane == row, spread, 0.0), axis=1,
                   keepdims=True)


def path_index(bins, nf, nb, paths, plen, width: int) -> jax.Array:
    """The kernel body over one (row block, tree block) -> (bt, bn)
    int32 leaf ids; see the module docstring for the equations."""
    bins_op, precision = gather_operand(bins)               # (bn, F)
    bn, F = bins.shape
    groups, _, lanes = paths.shape
    k = lanes // width
    bt, rows = groups * k, groups * lanes
    shift = width.bit_length() - 1

    f_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, F), 1)
    onehot = (f_iota == _column(nf, rows).astype(jnp.int32)).astype(
        bins_op.dtype)                                      # (bt*W, F)
    gathered = jax.lax.dot_general(
        onehot, bins_op, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)                 # (bt*W, bn)
    signs = jnp.where(gathered >= _column(nb, rows), 1.0, -1.0).astype(
        jnp.bfloat16).reshape(groups, lanes, bn)

    if k > 1:
        # the group's k path blocks on the diagonal of one (128, 128)
        tree_of_lane = jax.lax.broadcasted_iota(jnp.int32, paths.shape,
                                                2) >> shift
        zero = jnp.zeros_like(paths)
        paths = jnp.concatenate([jnp.where(tree_of_lane == i, paths, zero)
                                 for i in range(k)], axis=1)
    score = jax.lax.dot_general(
        paths, signs, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                 # (g, 128, bn)
    match = (score.reshape(rows, bn) == _column(plen, rows)).astype(
        jnp.bfloat16)                                       # (bt*W, bn)

    # idx[t] = sum_l l * match[t * W + l]: one more small matmul
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, rows), 1)
    t_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, rows), 0)
    leaf_of_row = jnp.where((r_iota >> shift) == t_iota,
                            r_iota & (width - 1), 0).astype(jnp.bfloat16)
    idx = jax.lax.dot_general(leaf_of_row, match, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return idx.astype(jnp.int32)


def _node_index_kernel(bins_ref, nf_ref, nb_ref, paths_ref, plen_ref,
                       out_ref, *, width):
    out_ref[...] = path_index(bins_ref[...], nf_ref[...], nb_ref[...],
                              paths_ref[...], plen_ref[...], width)


@functools.partial(jax.jit, static_argnames=("width", "block_n",
                                             "interpret"))
def node_index(bins: jax.Array, node_features: jax.Array,
               node_bins: jax.Array, paths: jax.Array, path_len: jax.Array,
               *, width: int, block_n: int = 256,
               interpret: bool = False) -> jax.Array:
    """idx^T[t, n] = the leaf row n reaches in tree t -> (T, N) int32.

    Inputs are the `nodes` layout's packed arrays (module docstring):
    W = `width` slots a tree, k = 128 / W trees a 128-lane row.  Each
    grid step takes `tree_block(width)` trees.  Pre-padded: N % block_n
    == 0 (a multiple of 128), the bins' F a multiple of 128, T a
    multiple of the tree block.  `bins` may be int32 or uint8.
    """
    if width & (width - 1) or not 8 <= width <= LANE:
        raise ValueError(f"node_index takes W a power of two in [8, "
                         f"{LANE}] node slots a tree, got {width}")
    block_t = tree_block(width)
    N, F = bins.shape
    groups, lanes = node_features.shape
    k = lanes // width
    T = groups * k
    if N % block_n or T % block_t:
        raise ValueError(
            f"node_index requires padded inputs: N={N} % block_n="
            f"{block_n} and T={T} % {block_t} must be 0")
    g = block_t // k
    row_spec = pl.BlockSpec((g, lanes), lambda i, j: (j, 0))
    return pl.pallas_call(
        functools.partial(_node_index_kernel, width=width),
        grid=(N // block_n, T // block_t),
        in_specs=[pl.BlockSpec((block_n, F), lambda i, j: (i, 0)),
                  row_spec, row_spec,
                  pl.BlockSpec((g, width, lanes), lambda i, j: (j, 0, 0)),
                  row_spec],
        out_specs=pl.BlockSpec((block_t, block_n), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.int32),
        compiler_params=tuning.compiler_params("parallel", "parallel"),
        interpret=interpret,
        name="node_index",
    )(bins, node_features, node_bins, paths, path_len)
