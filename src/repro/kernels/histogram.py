"""Pallas TPU kernel: gradient histogram accumulation for GBDT training.

Training-side hot-spot (the paper optimizes prediction; the framework
also owns training, whose inner loop is this histogram):

    hist[f, leaf*B + bin] += g[n]   for every sample n, feature f

On CPU/GPU this is a scatter-add; TPU has no fast scatter — the same
observation as the paper's CalculateLeafValues.  Same cure as well: turn
the scatter into one-hot matmuls.  The combined (leaf, bin) one-hot
factors into a bin one-hot and a leaf one-hot, and the leaf factor is
shared by every feature:

    GL[n, l*C + c] = [leaf[n] == l] * g[n, c]      (once per row block)
    hist_f         += onehot(bins[f])^T @ GL        (MXU, per feature)

so each feature costs one (n_bins, bn) x (bn, n_leaves*C) contraction,
with the stats sharing the lane axis with the leaves instead of owning
a lane dimension of their own.

Grid: (F / block_f, N / block_n) with N as the serial reduction axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning

# Stats are arbitrary float32 gradients/hessians: keep the MXU from
# rounding them to bfloat16.
STATS_PRECISION = jax.lax.Precision.HIGHEST


def _hist_kernel(bins_ref, leaf_ref, g_ref, e_leaf_ref, e_stat_ref,
                 out_ref):
    n_blk = pl.program_id(1)
    bf = bins_ref.shape[0]
    bp = out_ref.shape[1]                  # n_bins padded to 8 sublanes
    leaf = leaf_ref[...]                   # (bn, 1) int32
    g = g_ref[...]                         # (bn, C) f32
    e_leaf = e_leaf_ref[...]               # (n_leaves, n_leaves*C) 0/1
    bn = leaf.shape[0]
    n_leaves = e_leaf.shape[0]

    # Leaf-expanded stats: [leaf == l] spread over each leaf's C lanes,
    # times g tiled across the leaves.  Both expansions are exact
    # (0/1 matrices); the stats one runs at HIGHEST precision.
    leaf_onehot = (jax.lax.broadcasted_iota(jnp.int32, (bn, n_leaves), 1)
                   == leaf).astype(jnp.float32)
    gl = (jnp.dot(leaf_onehot, e_leaf, preferred_element_type=jnp.float32)
          * jnp.dot(g, e_stat_ref[...], precision=STATS_PRECISION,
                    preferred_element_type=jnp.float32))   # (bn, L*C)

    # Bin one-hot per feature: bins row (1, bn) against a sublane iota.
    # The v5e VPU has no 8-bit compare, so a uint8 row widens to int32
    # in registers — one (1, bn) row at a time, never the panel.
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (bp, bn), 0)

    @pl.when(n_blk == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    def feature(f, carry):
        row = bins_ref[f].astype(jnp.int32)                 # (1, bn)
        onehot = (bin_iota == row).astype(jnp.float32)      # (bp, bn)
        out_ref[f] += jnp.dot(onehot, gl, precision=STATS_PRECISION,
                              preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, bf, feature, 0)


@functools.partial(jax.jit, static_argnames=("n_bins", "n_leaves",
                                             "block_f", "block_n",
                                             "interpret"))
def histogram(bins_t: jax.Array, leaf: jax.Array, g: jax.Array, *,
              n_bins: int, n_leaves: int, block_f: int = 8,
              block_n: int = 256, interpret: bool = False) -> jax.Array:
    """bins_t: (F, N) int32 or uint8 feature-major bins; leaf: (N,)
    int32; g: (N, C) f32  ->  hist (F, n_leaves*n_bins, C) f32.

    Pre-padded: F % block_f == 0, N % block_n == 0 (block_n a multiple
    of 128); padded samples must carry g == 0 (they then contribute
    nothing).  Each feature's bins ride as a (1, N) row so any block_f
    tiles; the kernel's (F, n_bins, n_leaves*C) accumulator is
    rearranged into the (leaf, bin)-major contract here.
    """
    F, N = bins_t.shape
    C = g.shape[1]
    if F % block_f or N % block_n:
        raise ValueError(
            f"histogram requires padded inputs: F={F} % block_f={block_f} "
            f"and N={N} % block_n={block_n} must be 0 (use "
            "kernels.ops.histogram for automatic padding)")
    bp = -(-n_bins // tuning.SUBLANE) * tuning.SUBLANE
    lc = n_leaves * C
    lanes = jnp.arange(lc, dtype=jnp.int32)[None, :]
    e_leaf = (lanes // C == jnp.arange(n_leaves, dtype=jnp.int32)[:, None]
              ).astype(jnp.float32)                      # (L, L*C)
    e_stat = (lanes % C == jnp.arange(C, dtype=jnp.int32)[:, None]
              ).astype(jnp.float32)                      # (C, L*C)
    out = pl.pallas_call(
        _hist_kernel,
        grid=(F // block_f, N // block_n),
        in_specs=[
            pl.BlockSpec((block_f, 1, block_n), lambda i, j: (i, 0, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, C), lambda i, j: (j, 0)),
            pl.BlockSpec((n_leaves, lc), lambda i, j: (0, 0)),
            pl.BlockSpec((C, lc), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_f, bp, lc), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F, bp, lc), jnp.float32),
        compiler_params=tuning.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="histogram",
    )(bins_t.reshape(F, 1, N), leaf.reshape(N, 1), g, e_leaf, e_stat)
    hist = out[:, :n_bins].reshape(F, n_bins, n_leaves, C)
    return hist.transpose(0, 2, 1, 3).reshape(F, n_leaves * n_bins, C)


def histogram_ref(bins_t: jax.Array, leaf: jax.Array, g: jax.Array, *,
                  n_bins: int, n_leaves: int) -> jax.Array:
    """Pure-jnp oracle (the boosting trainer's segment_sum path).
    Accepts int32 or uint8 bins; promotion to int32 segment ids is
    benign here — the oracle optimizes for clarity, not bandwidth."""
    F, N = bins_t.shape
    seg = leaf[None, :] * n_bins + bins_t.astype(jnp.int32)  # (F, N)
    return jax.vmap(lambda s: jax.ops.segment_sum(
        g, s, num_segments=n_leaves * n_bins))(seg)
