"""Beyond-paper Pallas kernel: fully fused GBDT prediction.

binarize -> leaf_index -> leaf_gather executed in a single VMEM-resident
pass over a sample block.  The paper's three hotspots run as separate
passes with HBM round-trips between them; since GBDT inference is
memory-bound (sub-1 FLOP/byte on the scalar path), fusing removes the
intermediate `bins` (N x F int32) and `idx` (N x T int32) HBM traffic
entirely.  Binarized features are computed once per sample block at
t-block 0 into VMEM scratch and reused for every tree block (the grid's
T axis is serial on TPU).  The three stages are the standalone kernels'
own bodies (`binarize.count_borders`, `leaf_index.*_index`,
`leaf_gather.accumulate_leaves`), so fused and staged plans run the same
arithmetic, and all three kernels read the same class-major (bt, Cp, L)
leaf block.

Grid: (N / block_n, T / block_t).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning
from repro.kernels.binarize import count_borders
from repro.kernels.leaf_gather import accumulate_into, accumulate_leaves
from repro.kernels.leaf_index import (BP_TREE_BLOCK, bitplane_index,
                                      depth_major_index, soa_index)


def _binarize_once(x_ref, borders_ref, bins_scratch, n_borders: int):
    """Stage 1, once per sample block: bins into the VMEM scratch
    (uint8 when the ensemble fits 255 borders: 4x less VMEM held
    across every tree block — the quantized-pool representation,
    in-kernel)."""
    @pl.when(pl.program_id(1) == 0)
    def _binarize():
        bins_scratch[...] = count_borders(
            x_ref[...], borders_ref, n_borders).astype(bins_scratch.dtype)


def _fused_kernel(x_ref, borders_ref, sf_ref, sb_ref, lv_ref, out_ref,
                  idx_scratch, bins_scratch, *, n_borders: int):
    _binarize_once(x_ref, borders_ref, bins_scratch, n_borders)
    idx_scratch[...] = soa_index(bins_scratch[...], sf_ref[...],
                                 sb_ref[...])
    accumulate_into(out_ref, accumulate_leaves(idx_scratch, lv_ref))


def _fused_call(kernel, x, borders, model_args, model_specs, leaf_values,
                *, T, block_n, block_t, interpret, bins_scratch_dtype,
                name):
    N, F = x.shape
    B = borders.shape[0]
    _, C, L = leaf_values.shape
    if N % block_n or T % block_t:
        raise ValueError(
            f"{name} requires padded inputs: N={N} % block_n="
            f"{block_n} and T={T} % block_t={block_t} must be 0 "
            "(use kernels.ops.fused_predict for automatic padding)")
    return pl.pallas_call(
        functools.partial(kernel, n_borders=B),
        grid=(N // block_n, T // block_t),
        in_specs=[pl.BlockSpec((block_n, F), lambda i, j: (i, 0)),
                  pl.BlockSpec((B, F), lambda i, j: (0, 0))]
        + model_specs
        + [pl.BlockSpec((block_t, C, L), lambda i, j: (j, 0, 0))],
        out_specs=pl.BlockSpec((C, block_n), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, N), jnp.float32),
        # the tree-major index block stage 3 walks row by row, then the
        # binarized block (kept last: it is the fused kernel's own state)
        scratch_shapes=[pltpu.VMEM((block_t, block_n), jnp.int32),
                        pltpu.VMEM((block_n, F), bins_scratch_dtype)],
        compiler_params=tuning.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name=name,
    )(x, borders, *model_args, leaf_values)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_t", "interpret",
                                    "bins_scratch_dtype"))
def fused_predict(x: jax.Array, borders: jax.Array, split_features: jax.Array,
                  split_bins: jax.Array, leaf_values: jax.Array, *,
                  block_n: int = 128, block_t: int = 16,
                  interpret: bool = False,
                  bins_scratch_dtype=jnp.int32) -> jax.Array:
    """Fused GBDT predict -> (Cp, N) float32 (class-major, samples on
    lanes; `kernels.ops` transposes and drops the padded classes).

    Raw kernel entry: N and T must already be multiples of the block
    shapes (block_n a multiple of 128, block_t of 8), F a multiple of
    128, `leaf_values` the class-major (T, Cp, L) table with Cp a
    multiple of 8, and padded trees must carry zero leaf_values and
    split_bins > #bins (padded samples/features/classes are harmless
    zeros).
    `kernels.ops.fused_predict` is the public wrapper that performs that
    padding and picks the block shapes from the tuner — call it, not
    this, unless you have pre-padded tensors.  `bins_scratch_dtype`
    uint8 (valid when B <= 255) quarters the VMEM the binarized block
    holds across tree blocks; values are exact either way.
    """
    T, D = split_features.shape
    spec = pl.BlockSpec((block_t, D), lambda i, j: (j, 0))
    return _fused_call(_fused_kernel, x, borders,
                       (split_features, split_bins), [spec, spec],
                       leaf_values, T=T, block_n=block_n, block_t=block_t,
                       interpret=interpret,
                       bins_scratch_dtype=bins_scratch_dtype,
                       name="fused_predict")


def _fused_dm_kernel(x_ref, borders_ref, onehot_ref, sb_ref, pow2_ref,
                     lv_ref, out_ref, idx_scratch, bins_scratch, *,
                     n_borders: int):
    _binarize_once(x_ref, borders_ref, bins_scratch, n_borders)
    # Stage 2 via the PRECOMPUTED one-hot: the depth-major layout hoists
    # the iota / one-hot build to lower time.
    idx_scratch[...] = depth_major_index(bins_scratch[...], onehot_ref,
                                         sb_ref[...], pow2_ref)
    accumulate_into(out_ref, accumulate_leaves(idx_scratch, lv_ref))


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_t", "interpret",
                                    "bins_scratch_dtype"))
def fused_predict_dm(x: jax.Array, borders: jax.Array, onehot: jax.Array,
                     split_bins_dm: jax.Array, pow2: jax.Array,
                     leaf_values: jax.Array, *,
                     block_n: int = 128, block_t: int = 16,
                     interpret: bool = False,
                     bins_scratch_dtype=jnp.int32) -> jax.Array:
    """Fused GBDT predict over the depth-major lowered layout -> (Cp, N).

    Same contract as `fused_predict` with the model side replaced by
    the `DepthMajorLayout` arrays: `onehot` (T, D, F) f32 precomputed
    one-hot(sf), `split_bins_dm` (D, T) int32 bit planes (read
    tree-major: transposed here, a (T, D) int32 array), `pow2` (D, 1)
    f32.  N and T must be pre-padded to the block multiples.
    """
    T, D, F = onehot.shape
    return _fused_call(
        _fused_dm_kernel, x, borders, (onehot, split_bins_dm.T, pow2),
        [pl.BlockSpec((block_t, D, F), lambda i, j: (j, 0, 0)),
         pl.BlockSpec((block_t, D), lambda i, j: (j, 0)),
         pl.BlockSpec(memory_space=pltpu.SMEM)],
        leaf_values, T=T, block_n=block_n, block_t=block_t,
        interpret=interpret, bins_scratch_dtype=bins_scratch_dtype,
        name="fused_predict_dm")


def _fused_bp_kernel(x_ref, borders_ref, sf_ref, sb_ref, lv_ref, out_ref,
                     idx_scratch, bins_scratch, *, n_borders: int):
    _binarize_once(x_ref, borders_ref, bins_scratch, n_borders)
    # Stage 2 via the integer bit-plane pipeline (no MXU, no one-hot);
    # the leaf gather of stage 3 is where the MXU one-hot earns its keep.
    idx_scratch[...] = bitplane_index(bins_scratch[...], sf_ref[...],
                                      sb_ref[...]).T
    accumulate_into(out_ref, accumulate_leaves(idx_scratch, lv_ref))


@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret",
                                    "bins_scratch_dtype"))
def fused_predict_bp(x: jax.Array, borders: jax.Array,
                     split_features_bp: jax.Array, split_bins_bp: jax.Array,
                     leaf_values: jax.Array, *,
                     block_n: int = 128,
                     interpret: bool = False,
                     bins_scratch_dtype=jnp.int32) -> jax.Array:
    """Fused GBDT predict over the bitpacked lowered layout -> (Cp, N).

    Same contract as `fused_predict` with the model side replaced by
    the `BitpackedLayout` bit-plane arrays: `split_features_bp` /
    `split_bins_bp`, both (D, T).  Trees ride the lane axis, one
    128-tree lane per block: T and F must be multiples of 128.
    """
    D, T = split_features_bp.shape
    spec = pl.BlockSpec((D, BP_TREE_BLOCK), lambda i, j: (0, j))
    return _fused_call(
        _fused_bp_kernel, x, borders,
        (split_features_bp.astype(jnp.int32),
         split_bins_bp.astype(jnp.int32)), [spec, spec],
        leaf_values, T=T, block_n=block_n, block_t=BP_TREE_BLOCK,
        interpret=interpret, bins_scratch_dtype=bins_scratch_dtype,
        name="fused_predict_bp")
