"""Block-shape sweeps: the Pallas kernels must be exact under every
candidate BlockSpec tiling (the LMUL-analog tuning knob), and the VMEM
footprint model must keep every candidate under budget."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, tuning


@pytest.mark.parametrize("block_n,block_f", [(8, 128), (128, 128),
                                             (512, 256)])
def test_binarize_blocks(block_n, block_f):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(300, 60)).astype(np.float32))
    borders = jnp.asarray(np.sort(rng.normal(size=(31, 60)), 0)
                          .astype(np.float32))
    got = ops.binarize(x, borders, backend="pallas", block_n=block_n,
                       block_f=block_f)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.binarize(x, borders)))


@pytest.mark.parametrize("block_n,block_t", [(8, 8), (128, 32), (256, 64)])
def test_leaf_index_blocks(block_n, block_t):
    rng = np.random.default_rng(1)
    bins = jnp.asarray(rng.integers(0, 32, (200, 40)).astype(np.int32))
    sf = jnp.asarray(rng.integers(0, 40, (70, 6)).astype(np.int32))
    sb = jnp.asarray(rng.integers(1, 32, (70, 6)).astype(np.int32))
    got = ops.leaf_index(bins, sf, sb, backend="pallas", block_n=block_n,
                         block_t=block_t)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.leaf_index(bins, sf, sb)))


@pytest.mark.parametrize("block_n,block_t", [(64, 8), (128, 16), (256, 32)])
def test_fused_blocks(block_n, block_t):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(150, 30)).astype(np.float32))
    borders = jnp.asarray(np.sort(rng.normal(size=(15, 30)), 0)
                          .astype(np.float32))
    sf = jnp.asarray(rng.integers(0, 30, (50, 5)).astype(np.int32))
    sb = jnp.asarray(rng.integers(1, 15, (50, 5)).astype(np.int32))
    lv = jnp.asarray(rng.normal(size=(50, 32, 3)).astype(np.float32))
    got = ops.fused_predict(x, borders, sf, sb, lv, backend="pallas",
                            block_n=block_n, block_t=block_t)
    want = ref.fused_predict(x, borders, sf, sb, lv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_footprints_under_budget():
    cands = tuning.candidates_fused(F=200, D=8, L=256, C=7, n_borders=255)
    assert cands, "no candidate fits VMEM"
    for c in cands:
        assert c.footprint <= tuning.VMEM_BUDGET
    bn, bt = tuning.best_fused_blocks(200, 8, 256, 7, 255)
    assert bn >= 64 and bt >= 8


def test_footprint_model_counts_all_tiles():
    # covertype-scale: 54 features, depth 8, 7 classes.  The leaf block
    # is class-major (bt, 8, 256): one (8, 256) tile pair per tree, 512
    # KB a buffer at bt=64 and double-buffered (leaf-major, 7 classes on
    # 128 lanes, it would be 8 MB a buffer)
    leaf = (tuning._accumulate_stage_bytes(128, 64, 256, 7)
            - tuning._accumulate_stage_bytes(128, 8, 256, 7))
    assert leaf == 2 * (64 - 8) * 8 * 256 * 4
    assert tuning.tile_bytes((64, 7, 256), 4) == 512 * 1024
    # every tree-block-linear term (splits, per-depth one-hot, index
    # planes, leaf block) grows the fused tile with bt
    small = tuning.fused_footprint(128, 8, 54, 8, 256, 7, 255)
    big = tuning.fused_footprint(128, 64, 54, 8, 256, 7, 255)
    assert big - small > leaf