"""Pallas TPU kernel for feature binarization (paper: BinarizeFloatsNonSse).

The paper's RVV loop broadcasts each border against a vector of feature
values, compares (vmfgt_vf_f32m4_b8) and mask-adds ones (vadd_vv_u8m1_m),
accumulating the bin index.  The TPU adaptation tiles a (block_n, block_f)
sample x feature panel into VMEM and runs the same compare-accumulate over
the border axis on the 8x128 VPU; the border matrix for the feature panel
stays VMEM-resident for the whole sample block.

Grid: (N / block_n, F / block_f); borders are padded with +inf so that the
loop bound is a single static B for every feature.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning


def count_borders(x: jax.Array, borders_ref, n_borders: int) -> jax.Array:
    """#{b : x > borders[b]} per element -> int32, one border row per
    loop step.  The row is read through the ref (`pl.ds`), so the
    compiler emits a dynamic sublane load rather than slicing a value.
    Shared by the standalone binarize kernel and the fused kernels'
    stage 1."""
    def body(b, acc):
        return acc + (x > borders_ref[pl.ds(b, 1), :]).astype(jnp.int32)

    return jax.lax.fori_loop(0, n_borders, body,
                             jnp.zeros(x.shape, jnp.int32))


def _binarize_kernel(x_ref, borders_ref, out_ref, *, n_borders: int):
    # Accumulate in int32 (the compare-add loop), store in the output
    # dtype: uint8 for the quantized-pool path (the paper's one-byte bin
    # stream — vadd_vv_u8m1_m accumulates in u8 directly), int32 legacy.
    out_ref[...] = count_borders(x_ref[...], borders_ref,
                                 n_borders).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_f", "interpret",
                                    "out_dtype"))
def binarize(x: jax.Array, borders: jax.Array, *, block_n: int = 256,
             block_f: int = 128, interpret: bool = False,
             out_dtype=jnp.int32) -> jax.Array:
    """bins[n, f] = #{b : x[n, f] > borders[b, f]}  -> (N, F) `out_dtype`.

    Inputs must be pre-padded: N % block_n == 0, F % block_f == 0 (ops.py
    handles padding).  Padded border rows must be +inf.  `out_dtype`
    uint8 requires B <= 255 (validated in ops.py) and a block_n that is
    a multiple of 32 (uint8 stores tile (32, 128)).
    """
    N, F = x.shape
    B = borders.shape[0]
    grid = (N // block_n, F // block_f)
    return pl.pallas_call(
        functools.partial(_binarize_kernel, n_borders=B),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_f), lambda i, j: (i, j)),
            pl.BlockSpec((B, block_f), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, F), out_dtype),
        compiler_params=tuning.compiler_params("parallel", "parallel"),
        interpret=interpret,
        name="binarize",
    )(x, borders)
