"""Pallas TPU kernel for oblivious-tree leaf index computation
(paper: CalcIndexesBasic).

The paper's RVV loop hoists a pre-shifted ones vector (1 << depth) out of
the loop, then per depth compares binarized features against the split
border (vmsgeu) and mask-ORs the shifted bit into the running index.

TPU adaptation: the per-(tree, depth) feature *gather* bins[n, sf[t, d]] —
the strided-access pattern RVV also struggles with — is reformulated as a
one-hot matmul on the MXU: per depth d, onehot(sf[:, d]) (bt, F) against
the bins panel (bn, F) gathers the block's feature column for every tree
and sample in one systolic pass.  The bit-OR accumulation is the paper's
shifted-bit OR, verbatim.

Layout: the index comes out tree-major, (T, N) — trees on sublanes,
samples on lanes — so every block is lane-dense for any tree block that
is a multiple of 8 and the kernel never reshapes between the two axes.
`kernels.ops` transposes to the public (N, T) contract (within one jit,
XLA cancels that transpose against `leaf_gather`'s).

Grid: (N / block_n, T / block_t); the bins panel (block_n, F) is VMEM-
resident for all trees of the block row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning

# Bitpacked kernels put trees on the lane axis: one full lane per block.
BP_TREE_BLOCK = tuning.BITPLANE_TREE_BLOCK


def gather_operand(bins: jax.Array):
    """The bins panel as an MXU operand -> (operand, dot precision).

    uint8 bin ids (<= 255) are exact in bfloat16, so the one-hot gather
    runs as one bf16 pass.  The v5e has no uint8 -> float convert, so
    the panel widens through int32 first — the widening the contract
    checker sanctions, since its only sink is the matmul.  int32 bins
    (> 255 borders) may exceed bf16's exact integers and contract in
    float32 at HIGHEST precision instead."""
    if bins.dtype == jnp.uint8:
        return bins.astype(jnp.int32).astype(jnp.bfloat16), None
    return bins.astype(jnp.float32), jax.lax.Precision.HIGHEST


def index_planes(bins_op, precision, onehots, thresholds, weights):
    """idx^T (bt, bn) = sum_d weights[d] * [onehots[d] . bins >= thr[d]].

    `onehots[d]` is the (bt, F) feature selector of depth d,
    `thresholds[d]` the (bt, 1) split bins, `weights[d]` the depth's
    bit value (1 << d, or the depth-major layout's hoisted pow2).  The
    contraction is over the feature (lane) axis of both operands, so
    the (bt, bn) result needs no transpose."""
    bn = bins_op.shape[0]
    bt = onehots[0].shape[0]
    idx = jnp.zeros((bt, bn), jnp.float32)
    for onehot, thr, w in zip(onehots, thresholds, weights):
        gathered = jax.lax.dot_general(
            onehot.astype(bins_op.dtype), bins_op,
            (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)              # (bt, bn)
        idx = idx + jnp.where(gathered >= thr, w, 0.0)
    return idx.astype(jnp.int32)


def soa_index(bins, sf, sb):
    """`index_planes` over (bt, D) split arrays: the one-hot is built
    per depth from an iota compare against the split feature column."""
    bins_op, precision = gather_operand(bins)
    bt, D = sf.shape
    f_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, bins.shape[1]), 1)
    return index_planes(
        bins_op, precision,
        [f_iota == sf[:, d:d + 1] for d in range(D)],
        [sb[:, d:d + 1].astype(jnp.float32) for d in range(D)],
        [float(1 << d) for d in range(D)])


def depth_major_index(bins, onehot_ref, sb, pow2_ref):
    """`index_planes` over the depth-major lowered arrays: the one-hot
    rows arrive precomputed (the (bt, D, F) block's depth-d plane) and
    the bit values are the hoisted pow2 vector — no iota, no one-hot
    construction in the kernel."""
    bins_op, precision = gather_operand(bins)
    D = sb.shape[1]
    # pow2 lives in SMEM: each depth's bit value is a scalar read
    return index_planes(
        bins_op, precision,
        [onehot_ref[:, d, :] for d in range(D)],
        [sb[:, d:d + 1].astype(jnp.float32) for d in range(D)],
        [pow2_ref[d, 0] for d in range(D)])


def bitplane_index(bins, sf, sb):
    """Integer-only index assembly over (D, 128) bit planes -> (bn, 128).

    Per depth, each tree's split feature is gathered from the sample
    panel with a lane gather (trees and features share the lane axis,
    one 128-feature chunk at a time), compared against the threshold
    plane, and its bit OR-ed into the index register — no one-hot, no
    float, no MXU.  The v5e VPU has neither 8-bit compares nor 8-bit
    lane gathers, so a uint8 panel widens to int32 in registers first."""
    panel = bins.astype(jnp.int32)                   # (bn, F)
    bn, F = panel.shape
    D, bt = sf.shape
    idx = jnp.zeros((bn, bt), jnp.int32)
    for d in range(D):
        cols = jnp.zeros((bn, bt), jnp.int32)
        for k in range(0, F, bt):
            local = sf[d:d + 1, :] - k               # (1, bt)
            hit = (local >= 0) & (local < bt)
            lanes = jnp.broadcast_to(jnp.clip(local, 0, bt - 1), (bn, bt))
            got = jnp.take_along_axis(panel[:, k:k + bt], lanes, axis=1,
                                      mode="promise_in_bounds")
            cols = jnp.where(hit, got, cols)
        go = cols >= sb[d:d + 1, :]
        idx = idx | (go.astype(jnp.int32) << d)
    return idx


def _leaf_index_kernel(bins_ref, sf_ref, sb_ref, out_ref):
    out_ref[...] = soa_index(bins_ref[...], sf_ref[...], sb_ref[...])


def _tree_major_call(kernel, bins, model_args, model_specs, T, *,
                     block_n, block_t, interpret, name):
    """pallas_call shared by the leaf-index variants: grid over (row
    blocks, tree blocks), bins panel per row block, the tree-major
    (T, N) index out.  `name` names the kernel's op in a device trace."""
    N, F = bins.shape
    if N % block_n or T % block_t:
        raise ValueError(
            f"leaf index kernels require padded inputs: N={N} % block_n="
            f"{block_n} and T={T} % block_t={block_t} must be 0 "
            "(use kernels.ops for automatic padding)")
    return pl.pallas_call(
        kernel,
        grid=(N // block_n, T // block_t),
        in_specs=[pl.BlockSpec((block_n, F), lambda i, j: (i, 0))]
        + model_specs,
        out_specs=pl.BlockSpec((block_t, block_n), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.int32),
        compiler_params=tuning.compiler_params("parallel", "parallel"),
        interpret=interpret,
        name=name,
    )(bins, *model_args)


def _soa_call(bins, split_features, split_bins, *, block_n, block_t,
              interpret, name):
    T, D = split_features.shape
    spec = pl.BlockSpec((block_t, D), lambda i, j: (j, 0))
    return _tree_major_call(_leaf_index_kernel, bins,
                            (split_features, split_bins), [spec, spec], T,
                            block_n=block_n, block_t=block_t,
                            interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=("block_n", "block_t", "interpret"))
def leaf_index(bins: jax.Array, split_features: jax.Array,
               split_bins: jax.Array, *, block_n: int = 256,
               block_t: int = 16, interpret: bool = False) -> jax.Array:
    """idx^T[t, n] = sum_d 2^d [bins[n, sf[t,d]] >= sb[t,d]] -> (T, N) int32.

    Pre-padded: N % block_n == 0 (block_n a multiple of 128), T %
    block_t == 0 (block_t a multiple of 8).  Padded trees must use
    split_bins > max bin (e.g. 2^30) so they contribute leaf 0.
    """
    return _soa_call(bins, split_features, split_bins, block_n=block_n,
                     block_t=block_t, interpret=interpret, name="leaf_index")


def _leaf_index_dm_kernel(bins_ref, onehot_ref, sb_ref, pow2_ref, out_ref):
    out_ref[...] = depth_major_index(bins_ref[...], onehot_ref,
                                     sb_ref[...], pow2_ref)


@functools.partial(jax.jit, static_argnames=("block_n", "block_t",
                                             "interpret"))
def leaf_index_dm(bins: jax.Array, onehot: jax.Array, split_bins_dm: jax.Array,
                  pow2: jax.Array, *, block_n: int = 256, block_t: int = 16,
                  interpret: bool = False) -> jax.Array:
    """Depth-major `leaf_index`: gather via the precomputed one-hot
    matrix -> (T, N) int32.

    Inputs are the depth-major lowered model arrays (see
    `repro.core.layout.DepthMajorLayout`): `onehot` (T, D, F) f32,
    `split_bins_dm` (D, T) int32 bit-plane order, `pow2` (D, 1) f32.
    The kernel reads the thresholds tree-major, so the (D, T) planes are
    transposed once here (a (T, D) int32 array; the one-hot, which
    dominates, is read in place).  Pre-padded: N % block_n == 0, T %
    block_t == 0, padded trees carry split_bins > max bin.  `bins` may
    be int32 or uint8 (the quantized-pool stream).
    """
    T, D, F = onehot.shape
    return _tree_major_call(
        _leaf_index_dm_kernel, bins,
        (onehot, split_bins_dm.T, pow2),
        [pl.BlockSpec((block_t, D, F), lambda i, j: (j, 0, 0)),
         pl.BlockSpec((block_t, D), lambda i, j: (j, 0)),
         pl.BlockSpec(memory_space=pltpu.SMEM)], T,
        block_n=block_n, block_t=block_t, interpret=interpret,
        name="leaf_index_dm")


def _leaf_index_bp_kernel(bins_ref, sf_ref, sb_ref, out_ref):
    # Bitpacked lowered layout: integer-only pipeline, the closest TPU
    # analog of the paper's RVV loop (see `bitplane_index`).
    out_ref[...] = bitplane_index(bins_ref[...], sf_ref[...],
                                  sb_ref[...]).T


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def leaf_index_bp(bins: jax.Array, split_features_bp: jax.Array,
                  split_bins_bp: jax.Array, *, block_n: int = 256,
                  interpret: bool = False) -> jax.Array:
    """Bitpacked `leaf_index`: integer lane-gather + shift/or index
    assembly -> (T, N) int32.

    Inputs are the bitpacked lowered model arrays (see
    `repro.core.layout.BitpackedLayout`): bit-plane transposed
    `split_features_bp` / `split_bins_bp`, both (D, T).  Trees ride the
    lane axis, one 128-tree lane per block.  Pre-padded: N % block_n ==
    0, T and F multiples of 128, padded trees carry split_bins > max
    bin (they take bit 0 at every depth -> leaf 0).
    """
    D, T = split_features_bp.shape
    if bins.shape[1] % BP_TREE_BLOCK:
        raise ValueError(f"leaf_index_bp gathers 128-feature lanes: "
                         f"F={bins.shape[1]} must be a multiple of 128")
    spec = pl.BlockSpec((D, BP_TREE_BLOCK), lambda i, j: (0, j))
    return _tree_major_call(
        _leaf_index_bp_kernel, bins,
        (split_features_bp.astype(jnp.int32),
         split_bins_bp.astype(jnp.int32)), [spec, spec], T,
        block_n=block_n, block_t=BP_TREE_BLOCK, interpret=interpret,
        name="leaf_index_bp")


@functools.partial(jax.jit, static_argnames=("block_n", "block_t", "interpret"))
def leaf_index_u8(bins: jax.Array, split_features: jax.Array,
                  split_bins: jax.Array, *, block_n: int = 256,
                  block_t: int = 16, interpret: bool = False) -> jax.Array:
    """`leaf_index` over the quantized-pool bin stream: uint8 bins.

    Mirrors the paper's CalcIndexesBasic loop, which runs entirely on
    the *quantized* uint8 representation — binarization never reruns per
    tree.  The kernel body is shared with the int32 variant; this entry
    pins the dtype contract and keeps the 4x-narrower bins panel
    (block_n x F bytes instead of words) VMEM-resident per sample block.
    uint8 blocks tile (32, 128), which every lane-multiple block_n fits.
    """
    if bins.dtype != jnp.uint8:
        raise TypeError(f"leaf_index_u8 takes uint8 bins, got {bins.dtype} "
                        "(use leaf_index for int32)")
    return _soa_call(bins, split_features, split_bins, block_n=block_n,
                     block_t=block_t, interpret=interpret,
                     name="leaf_index_u8")
