"""Quantized-first evaluation API + kernel registry: pool-vs-float
parity across backends, uint8 bin-range edges, schema-fingerprint
safety, zero-binarize accounting, border-computation edge cases, and
registry introspection."""
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import predict, quantize
from repro.core.predictor import PredictConfig, Predictor
from repro.core.quantize import QuantizedPool, quantize_pool
from repro.core.trees import ObliviousEnsemble
from repro.kernels import ops, ref, registry


def _rand_ensemble(seed=3, n_trees=13, depth=4, n_features=11,
                   n_borders=9, n_outputs=2, borders=None):
    rng = np.random.default_rng(seed)
    if borders is None:
        borders = jnp.asarray(
            np.sort(rng.normal(size=(n_borders, n_features)), 0)
            .astype(np.float32))
    sf = jnp.asarray(rng.integers(0, n_features,
                                  (n_trees, depth)).astype(np.int32))
    sb = jnp.asarray(rng.integers(1, n_borders,
                                  (n_trees, depth)).astype(np.int32))
    lv = jnp.asarray(rng.normal(size=(n_trees, 2 ** depth, n_outputs))
                     .astype(np.float32))
    return ObliviousEnsemble(sf, sb, lv, borders,
                             jnp.full((n_features,), n_borders, jnp.int32))


def _rand_x(ens, n=37, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, ens.n_features))
                       .astype(np.float32))


# --------------------------------------------------------------------------
# compute_borders edge cases (satellite)
# --------------------------------------------------------------------------
def test_compute_borders_validates_max_bins():
    x = np.random.default_rng(0).normal(size=(20, 3)).astype(np.float32)
    with pytest.raises(ValueError, match="max_bins"):
        quantize.compute_borders(x, max_bins=257)
    with pytest.raises(ValueError, match="max_bins"):
        quantize.compute_borders(x, max_bins=1)
    borders, counts = quantize.compute_borders(x, max_bins=256)
    assert borders.shape == (255, 3)
    assert int(counts.max()) <= 255


def test_compute_borders_constant_and_nan_columns():
    rng = np.random.default_rng(1)
    x = np.stack([
        rng.normal(size=64),                  # normal column
        np.full(64, 2.5),                     # constant
        np.full(64, np.nan),                  # all-NaN
        np.full(64, np.inf),                  # all-inf (non-finite)
    ], axis=1).astype(np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")        # the old path warned here
        borders, counts = quantize.compute_borders(x, max_bins=16)
    counts = np.asarray(counts)
    assert counts[0] > 0
    # constant / all-NaN / all-inf columns: no border separates anything
    assert counts[1] == counts[2] == counts[3] == 0
    b = np.asarray(borders)
    assert np.all(np.isinf(b[:, 1:]))
    assert borders.dtype == jnp.float32
    # borders never sit at the column max (x > border must be non-trivial)
    assert np.all(b[:counts[0], 0] < x[:, 0].max())


def test_binarize_matrix_shim_matches_registry_path():
    ens = _rand_ensemble()
    x = _rand_x(ens, 10)
    got = quantize.binarize_matrix(x, ens.borders)
    want = ops.binarize(x, ens.borders, backend="ref")
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# Quantized-vs-float parity across registry backends (satellite)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("cfg", [
    PredictConfig(strategy="staged", backend="ref"),
    PredictConfig(strategy="staged", backend="pallas"),
    PredictConfig(strategy="fused", backend="ref"),
    PredictConfig(strategy="fused", backend="pallas"),
    PredictConfig(strategy="staged", backend="ref", tree_block=4),
    PredictConfig(strategy="staged", backend="pallas", tree_block=4),
])
def test_pool_matches_float_path(cfg):
    ens = _rand_ensemble()
    x = _rand_x(ens, 37)
    plan = Predictor.build(ens, cfg, expected_batch=37)
    pool = plan.quantize(x)
    assert pool.bins.dtype == jnp.uint8
    assert pool.bins.shape == (37, ens.n_features)
    np.testing.assert_allclose(np.asarray(plan.raw(x)),
                               np.asarray(plan.raw(pool)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(plan.proba(x)),
                               np.asarray(plan.proba(pool)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(plan.classify(x)),
                                  np.asarray(plan.classify(pool)))


def test_pool_parity_across_backends():
    # One pool, every backend/strategy: all must agree with the oracle.
    ens = _rand_ensemble(seed=11)
    x = _rand_x(ens, 20)
    want = np.asarray(ref.fused_predict(x, ens.borders, ens.split_features,
                                        ens.split_bins, ens.leaf_values))
    pool = quantize_pool(x, ens.borders, backend="ref")
    for cfg in (PredictConfig(strategy="staged", backend="ref"),
                PredictConfig(strategy="staged", backend="pallas"),
                PredictConfig(strategy="fused", backend="pallas")):
        plan = Predictor.build(ens, cfg, expected_batch=20)
        got = np.asarray(plan.raw(pool))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_legacy_kwarg_path_accepts_pool():
    ens = _rand_ensemble()
    x = _rand_x(ens, 16)
    plan = Predictor.build(ens, strategy="staged", backend="ref")
    pool = plan.quantize(x)
    np.testing.assert_allclose(
        np.asarray(predict.raw_predict(ens, pool, strategy="staged",
                                       backend="ref")),
        np.asarray(plan.raw(x)), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# uint8 bin-range edge: 255 borders, last bin id 255 (satellite)
# --------------------------------------------------------------------------
def test_bin_id_255_last_border_edge():
    rng = np.random.default_rng(5)
    F = 3
    borders = jnp.asarray(np.sort(rng.normal(size=(255, F)), 0)
                          .astype(np.float32))
    # rows below the lowest border, between, and above the highest
    x = jnp.asarray(np.stack([
        np.asarray(borders[0]) - 1.0,
        np.asarray(borders[127]),
        np.asarray(borders[254]) + 1.0,
    ]).astype(np.float32))
    b_i32 = np.asarray(ops.binarize(x, borders, backend="ref"))
    for backend in ("ref", "pallas"):
        b_u8 = np.asarray(ops.binarize_u8(x, borders, backend=backend))
        assert b_u8.dtype == np.uint8
        np.testing.assert_array_equal(b_u8.astype(np.int32), b_i32)
    assert b_i32.max() == 255          # the uint8 ceiling, exactly
    assert b_i32.min() == 0
    # leaf_index over u8 bins must agree with the int32 stream even when
    # split_bins reference the last border (id 255)
    sf = jnp.asarray(np.array([[0, 1], [2, 2]], np.int32))
    sb = jnp.asarray(np.array([[255, 128], [1, 255]], np.int32))
    want = np.asarray(ref.leaf_index(jnp.asarray(b_i32), sf, sb))
    for backend in ("ref", "pallas"):
        got = np.asarray(ops.leaf_index(jnp.asarray(b_u8), sf, sb,
                                        backend=backend))
        np.testing.assert_array_equal(got, want)


def test_u8_binarize_rejects_too_many_borders():
    rng = np.random.default_rng(6)
    borders = jnp.asarray(np.sort(rng.normal(size=(256, 2)), 0)
                          .astype(np.float32))
    x = jnp.asarray(rng.normal(size=(4, 2)).astype(np.float32))
    for backend in ("ref", "pallas"):
        with pytest.raises(ValueError, match="255"):
            ops.binarize_u8(x, borders, backend=backend)
    with pytest.raises(ValueError, match="255"):
        quantize_pool(x, borders)


# --------------------------------------------------------------------------
# Schema fingerprint (satellite)
# --------------------------------------------------------------------------
def test_pool_fingerprint_mismatch_raises():
    ens_a = _rand_ensemble(seed=1)
    ens_b = _rand_ensemble(seed=1,
                           borders=ens_a.borders + np.float32(0.25))
    plan_a = Predictor.build(ens_a, strategy="staged", backend="ref")
    plan_b = Predictor.build(ens_b, strategy="staged", backend="ref")
    pool = plan_a.quantize(_rand_x(ens_a, 8))
    with pytest.raises(ValueError, match="schema mismatch"):
        plan_b.raw(pool)
    with pytest.raises(ValueError, match="schema mismatch"):
        plan_b.raw_uncached(pool)
    # same borders -> shareable pool, regardless of plan config
    plan_a2 = Predictor.build(ens_a, strategy="fused", backend="pallas",
                              expected_batch=8)
    np.testing.assert_allclose(np.asarray(plan_a2.raw(pool)),
                               np.asarray(plan_a.raw(pool)),
                               rtol=1e-5, atol=1e-5)


def test_pool_validates_shape_and_dtype():
    with pytest.raises(ValueError, match="uint8"):
        QuantizedPool(jnp.zeros((4, 3), jnp.int32), "abc")
    with pytest.raises(ValueError, match="N, F"):
        QuantizedPool(jnp.zeros((4,), jnp.uint8), "abc")


def test_pool_slice_and_pad_rows():
    ens = _rand_ensemble()
    plan = Predictor.build(ens, strategy="staged", backend="ref")
    pool = plan.quantize(_rand_x(ens, 10))
    part = pool.slice_rows(2, 7)
    assert len(part) == 5 and part.fingerprint == pool.fingerprint
    padded = part.pad_rows(8)
    assert len(padded) == 8
    assert np.all(np.asarray(padded.bins)[5:] == 0)     # bin-0 pad rows
    with pytest.raises(ValueError, match="pad"):
        padded.pad_rows(4)
    np.testing.assert_allclose(
        np.asarray(plan.raw(padded))[:5],
        np.asarray(plan.raw(pool))[2:7], rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# Zero-binarize accounting on the pool path (acceptance criterion)
# --------------------------------------------------------------------------
def test_zero_binarize_dispatches_when_scoring_pool():
    ens = _rand_ensemble()
    x = _rand_x(ens, 16)
    plan = Predictor.build(ens, strategy="staged", backend="ref")
    pool = plan.quantize(x)
    registry.reset_call_stats()
    for _ in range(3):
        plan.raw(pool)
    stats = registry.call_stats()
    assert stats.get("binarize", 0) == 0, stats
    assert stats.get("leaf_index", 0) >= 1       # the pool path did run
    assert "binarize" not in registry.dispatched_impls()
    # the float path, by contrast, dispatches binarize
    plan.raw(x)
    assert registry.call_stats().get("binarize", 0) >= 1
    assert registry.dispatched_impls()["binarize"] == ["ref"]


# --------------------------------------------------------------------------
# Registry introspection + legacy shim (satellite / acceptance)
# --------------------------------------------------------------------------
def test_registry_lists_every_op_with_ref_and_pallas():
    rows = registry.table()
    by_op = {}
    for r in rows:
        by_op.setdefault(r["op"], set()).add(r["impl"])
    for op in registry.CORE_OPS:
        assert op in by_op, f"op {op} missing from registry"
        assert len(by_op[op]) >= 2, f"op {op} has < 2 implementations"
        assert {"ref", "pallas"} <= by_op[op]
    # u8 bin-stream variants exist where the dtype matters
    assert "pallas_u8" in by_op["leaf_index"]
    assert {"ref_u8", "pallas_u8"} <= by_op["binarize"]
    assert registry.known_backends() == ("pallas", "ref")
    # the rendered table carries one line per row plus a two-line header
    assert len(registry.format_table().splitlines()) == len(rows) + 2


def test_registry_resolve_and_errors():
    assert registry.resolve("binarize", "ref") == "ref"
    assert registry.resolve("binarize", "ref", dtype="uint8") == "ref_u8"
    assert registry.resolve("binarize", "auto") in ("ref", "pallas")
    assert registry.resolve("leaf_index", "ref", dtype="uint8") == "ref"
    with pytest.raises(KeyError, match="no implementation"):
        registry.resolve("binarize", "cuda")
    # histogram became a registered op (the training side); a truly
    # unknown op still raises
    assert registry.resolve("histogram", "ref") == "ref"
    with pytest.raises(KeyError, match="unknown kernel op"):
        registry.resolve("treeshap", "ref")
    with pytest.raises(ValueError, match="does not handle"):
        registry.resolve("leaf_gather", "pallas", dtype="uint8")
    with pytest.raises(ValueError):
        PredictConfig(backend="cuda")


def test_legacy_backend_kwarg_is_registry_shim():
    ens = _rand_ensemble()
    x = _rand_x(ens, 9)
    via_kwarg = ops.binarize(x, ens.borders, backend="pallas")
    via_registry = registry.dispatch("binarize", "pallas", x, ens.borders,
                                     block_n=256, block_f=128)
    np.testing.assert_array_equal(np.asarray(via_kwarg),
                                  np.asarray(via_registry))
    # exact impl names are valid backend values on the op shims
    u8 = ops.binarize(x, ens.borders, backend="ref_u8")
    assert u8.dtype == jnp.uint8


# --------------------------------------------------------------------------
# Serving: shared-quantizer path (tentpole integration)
# --------------------------------------------------------------------------
def test_registry_predict_multi_shares_quantizer():
    from repro.serving.engine import ModelRegistry
    ens_a = _rand_ensemble(seed=21, n_trees=8)
    ens_b = _rand_ensemble(seed=22, n_trees=6, borders=ens_a.borders)
    ens_c = _rand_ensemble(seed=23, n_trees=7)     # different schema
    reg = ModelRegistry(max_batch=32,
                        config=PredictConfig(strategy="staged",
                                             backend="ref"))
    try:
        reg.register("a", ens_a)
        reg.register("b", ens_b)
        reg.register("c", ens_c)
        assert reg.get("a").schema_fingerprint == \
            reg.get("b").schema_fingerprint
        assert reg.get("a").schema_fingerprint != \
            reg.get("c").schema_fingerprint
        xs = np.asarray(_rand_x(ens_a, 50))
        registry.reset_call_stats()
        multi = reg.predict_multi(xs)
        # 2 schemas -> exactly 2 binarize dispatches for 3 models
        assert registry.call_stats().get("binarize", 0) == 2
        for name in ("a", "b", "c"):
            np.testing.assert_allclose(multi[name],
                                       reg.predict_batch(name, xs),
                                       rtol=1e-5, atol=1e-6)
    finally:
        reg.close()


def test_server_predict_pool_bucketed():
    from repro.serving.engine import GBDTServer
    ens = _rand_ensemble(seed=31)
    server = GBDTServer(ens, config=PredictConfig(strategy="staged",
                                                  backend="ref"),
                        max_batch=16, min_bucket=4)
    try:
        xs = np.asarray(_rand_x(ens, 41))          # forces chunking
        pool = server.quantize(xs)
        np.testing.assert_allclose(server.predict_pool(pool),
                                   server.predict_batch(xs),
                                   rtol=1e-5, atol=1e-6)
        # retraces stay bounded: every chunk was padded to a bucket
        shapes = {s for s in server.predictor.stats["entry_shapes"]
                  if s[0] == "proba_pool"}
        assert all(s[1] in server.buckets for s in shapes), shapes
    finally:
        server.close()
