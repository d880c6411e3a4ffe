"""Pallas kernel validation: interpret=True vs pure-jnp oracles, shape sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import binarize as bk
from repro.kernels import fused_predict as fk
from repro.kernels import l2dist as lk
from repro.kernels import leaf_gather as gk
from repro.kernels import leaf_index as ik


def _toy_ensemble(rng, T, D, F, C, n_bins=32):
    sf = rng.integers(0, F, size=(T, D)).astype(np.int32)
    sb = rng.integers(1, n_bins, size=(T, D)).astype(np.int32)
    lv = rng.normal(size=(T, 2 ** D, C)).astype(np.float32)
    return jnp.asarray(sf), jnp.asarray(sb), jnp.asarray(lv)


def _borders(rng, B, F):
    b = np.sort(rng.normal(size=(B, F)).astype(np.float32), axis=0)
    return jnp.asarray(b)


@pytest.mark.parametrize("N,F,B", [(256, 128, 16), (100, 52, 32),
                                   (513, 200, 255), (32, 1, 1)])
def test_binarize_kernel(N, F, B):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(N, F)).astype(np.float32))
    borders = _borders(rng, B, F)
    got = ops.binarize(x, borders, backend="pallas")
    want = ref.binarize(x, borders)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("N,F,T,D", [(256, 128, 16, 6), (100, 52, 37, 8),
                                     (64, 90, 100, 1), (512, 512, 8, 4)])
def test_leaf_index_kernel(N, F, T, D):
    rng = np.random.default_rng(1)
    bins = jnp.asarray(rng.integers(0, 32, size=(N, F)).astype(np.int32))
    sf, sb, _ = _toy_ensemble(rng, T, D, F, 1)
    got = ops.leaf_index(bins, sf, sb, backend="pallas")
    want = ref.leaf_index(bins, sf, sb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("N,T,D,C", [(128, 16, 6, 1), (100, 37, 8, 7),
                                     (64, 100, 4, 20), (256, 8, 1, 2),
                                     (96, 24, 5, 8), (130, 19, 3, 9)])
def test_leaf_gather_kernel(N, T, D, C):
    rng = np.random.default_rng(2)
    idx = jnp.asarray(rng.integers(0, 2 ** D, size=(N, T)).astype(np.int32))
    _, _, lv = _toy_ensemble(rng, T, D, 8, C)
    got = ops.leaf_gather(idx, lv, backend="pallas")
    want = ref.leaf_gather(idx, lv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("N,K", [(256, 128), (100, 512), (37, 90), (8, 8)])
def test_l2_rowwise_kernel(N, K):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(K,)).astype(np.float32))
    refs = jnp.asarray(rng.normal(size=(N, K)).astype(np.float32))
    got = ops.l2sq_rowwise(q, refs, backend="pallas")
    want = ref.l2sq_rowwise(q, refs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("M,N,K", [(128, 128, 128), (100, 200, 512),
                                   (37, 61, 90), (300, 50, 256)])
def test_l2_matrix_kernel(M, N, K):
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(N, K)).astype(np.float32))
    got = ops.l2sq_matrix(a, b, backend="pallas")
    want = ref.l2sq_matrix(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("N,F,T,D,C,B", [(128, 52, 16, 6, 1, 32),
                                         (100, 90, 40, 6, 1, 255),
                                         (64, 54, 24, 8, 7, 16),
                                         (200, 512, 10, 4, 20, 64)])
def test_fused_predict_kernel(N, F, T, D, C, B):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(N, F)).astype(np.float32))
    borders = _borders(rng, B, F)
    sf, sb, lv = _toy_ensemble(rng, T, D, F, C, n_bins=B)
    got = ops.fused_predict(x, borders, sf, sb, lv, backend="pallas")
    want = ref.fused_predict(x, borders, sf, sb, lv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_pipeline_composition_matches_fused():
    """binarize |> leaf_index |> leaf_gather == fused_predict (both backends)."""
    rng = np.random.default_rng(6)
    N, F, T, D, C, B = 90, 46, 50, 6, 1, 128
    x = jnp.asarray(rng.normal(size=(N, F)).astype(np.float32))
    borders = _borders(rng, B, F)
    sf, sb, lv = _toy_ensemble(rng, T, D, F, C, n_bins=B)
    for backend in ("ref", "pallas"):
        bins = ops.binarize(x, borders, backend=backend)
        idx = ops.leaf_index(bins, sf, sb, backend=backend)
        staged = ops.leaf_gather(idx, lv, backend=backend)
        fused = ops.fused_predict(x, borders, sf, sb, lv, backend=backend)
        np.testing.assert_allclose(np.asarray(staged), np.asarray(fused),
                                   rtol=1e-5, atol=1e-4)


def test_border_edge_semantics():
    """Strict '>' border semantics: x == border stays in the lower bin."""
    x = jnp.asarray([[0.0, 1.0, 1.5, 2.0, 2.5]], dtype=jnp.float32).T
    borders = jnp.asarray([[1.0], [2.0]], dtype=jnp.float32)
    x = x.reshape(5, 1)
    got_ref = ref.binarize(x, borders)
    got_pl = ops.binarize(x, borders, backend="pallas")
    np.testing.assert_array_equal(np.asarray(got_ref).ravel(),
                                  [0, 0, 1, 1, 2])
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(got_ref))


@pytest.mark.parametrize("F,N,C,B,L", [(8, 256, 1, 16, 8), (6, 100, 7, 32, 4),
                                       (16, 512, 3, 8, 16)])
def test_histogram_kernel(F, N, C, B, L):
    from repro.kernels import histogram as hk
    rng = np.random.default_rng(7)
    bins_t = rng.integers(0, B, (F, N)).astype(np.int32)
    leaf = rng.integers(0, L, (N,)).astype(np.int32)
    g = rng.normal(size=(N, C)).astype(np.float32)
    Fp = ((F + 7) // 8) * 8
    Np = ((N + 255) // 256) * 256
    bt = np.zeros((Fp, Np), np.int32)
    bt[:F, :N] = bins_t
    lf = np.zeros((Np,), np.int32)
    lf[:N] = leaf
    gg = np.zeros((Np, C), np.float32)
    gg[:N] = g                       # padded samples carry g == 0
    got = hk.histogram(jnp.asarray(bt), jnp.asarray(lf), jnp.asarray(gg),
                       n_bins=B, n_leaves=L, interpret=True)[:F]
    want = hk.histogram_ref(jnp.asarray(bins_t), jnp.asarray(leaf),
                            jnp.asarray(g), n_bins=B, n_leaves=L)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("in_dtype", [np.float32, jnp.bfloat16])
def test_binarize_dtype_sweep(in_dtype):
    """bf16 inputs: binarize via f32 cast matches the f32 oracle on the
    bf16-representable values."""
    rng = np.random.default_rng(8)
    x32 = rng.normal(size=(64, 20)).astype(np.float32)
    x = jnp.asarray(x32).astype(in_dtype)
    borders = jnp.asarray(np.sort(rng.normal(size=(9, 20)), 0)
                          .astype(np.float32))
    got = ops.binarize(x.astype(jnp.float32), borders, backend="pallas")
    want = ref.binarize(x.astype(jnp.float32), borders)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("in_dtype", [np.float32, jnp.bfloat16])
def test_l2_dtype_sweep(in_dtype):
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.normal(size=(40, 64)).astype(np.float32)).astype(
        in_dtype).astype(jnp.float32)
    b = jnp.asarray(rng.normal(size=(30, 64)).astype(np.float32)).astype(
        in_dtype).astype(jnp.float32)
    got = ops.l2sq_matrix(a, b, backend="pallas")
    want = ref.l2sq_matrix(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("bins_dtype", [np.int32, np.uint8])
def test_leaf_index_bins_dtype_sweep(bins_dtype):
    """u8 bin storage (CatBoost's on-disk format) -> i32 compute."""
    rng = np.random.default_rng(10)
    bins = rng.integers(0, 32, (100, 24)).astype(bins_dtype)
    sf = jnp.asarray(rng.integers(0, 24, (20, 6)).astype(np.int32))
    sb = jnp.asarray(rng.integers(1, 32, (20, 6)).astype(np.int32))
    got = ops.leaf_index(jnp.asarray(bins.astype(np.int32)), sf, sb,
                         backend="pallas")
    want = ref.leaf_index(jnp.asarray(bins.astype(np.int32)), sf, sb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
