"""Compiled-plan Predictor API: config validation + one-time resolution,
plan-cache bounds (recompiles per batch shape), prepare-once model
padding, parity with the legacy kwarg path, CatBoost JSON ingestion,
and ensemble concat/slice validation."""
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import predict
from repro.core.predictor import (PredictConfig, Predictor,
                                  load_catboost_json)
from repro.core.trees import (ObliviousEnsemble, PAD_SPLIT_BIN,
                              concat_ensembles)
from repro.kernels import ops, ref


def _rand_ensemble(seed=3, n_trees=13, depth=4, n_features=11,
                   n_borders=9, n_outputs=2):
    rng = np.random.default_rng(seed)
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
# PredictConfig
# --------------------------------------------------------------------------
def test_config_validation():
    with pytest.raises(ValueError):
        PredictConfig(strategy="warp")
    with pytest.raises(ValueError):
        PredictConfig(backend="cuda")
    with pytest.raises(ValueError):
        PredictConfig(tree_block=-1)
    with pytest.raises(ValueError):
        PredictConfig(block_n=0)
    with pytest.raises(ValueError):
        PredictConfig(block_t=-8)


def test_config_resolves_auto_once():
    ens = _rand_ensemble()
    cfg = PredictConfig()          # everything auto
    assert not cfg.is_resolved
    r = cfg.resolve(ens, n_rows=64)
    assert r.is_resolved
    assert r.strategy in ("staged", "fused")
    assert r.backend in ("pallas", "ref")
    # fused plans always carry concrete block shapes
    rf = PredictConfig(strategy="fused").resolve(ens, n_rows=64)
    assert rf.block_n is not None and rf.block_t is not None
    # resolving a resolved config is a no-op
    assert r.resolve(ens) == r


def test_build_rejects_config_and_kwargs():
    ens = _rand_ensemble()
    with pytest.raises(TypeError):
        Predictor.build(ens, PredictConfig(), strategy="staged")
    # kwargs-only convenience form works
    plan = Predictor.build(ens, strategy="staged", backend="ref")
    assert plan.config.strategy == "staged"


# --------------------------------------------------------------------------
# Plan cache + prepare-once padding (the acceptance criteria)
# --------------------------------------------------------------------------
def test_plan_cache_bounded_by_batch_shapes():
    ens = _rand_ensemble()
    plan = Predictor.build(ens, strategy="staged", backend="ref")
    x = _rand_x(ens, 64)
    for n in (16, 16, 16, 32, 16, 32):
        plan.raw(x[:n])
    s = plan.stats
    # recompiles are bounded by distinct batch shapes, not call count
    assert s["traces"]["raw"] == 2, s
    assert s["cache_entries"] == 2
    plan.proba(x[:16])             # separate entry point, own cache
    assert plan.stats["traces"]["proba"] == 1
    assert plan.stats["total_traces"] == 3


def test_model_padded_once_then_zero_model_pads():
    # The core acceptance check: after build, repeated fixed-batch
    # predicts trigger zero model-side jnp.pad ops and <= 1 XLA trace.
    ens = _rand_ensemble()
    x = _rand_x(ens, 16)
    ops.reset_pad_stats()
    plan = Predictor.build(ens, PredictConfig(strategy="fused",
                                              backend="pallas"),
                           expected_batch=16)
    build_pads = ops.pad_stats()["model"]
    assert build_pads > 0                       # unpadded model: F, T pads
    assert plan.stats["build_model_pads"] == build_pads
    ops.reset_pad_stats()
    outs = [plan.raw(x) for _ in range(3)]
    assert ops.pad_stats()["model"] == 0        # zero model-side pads
    assert plan.stats["traces"]["raw"] == 1     # one compile at fixed batch
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[-1]))


@pytest.mark.parametrize("strategy", ["staged", "fused"])
def test_lowered_model_is_an_argument_not_a_constant(strategy):
    # The jitted entries take the lowered model as an argument: baked
    # in as a constant it would be copied into every executable (one
    # device copy per entry and batch bucket).  The program text must
    # not grow with the tree count.
    def program_text(n_trees):
        ens = _rand_ensemble(n_trees=n_trees)
        plan = Predictor.build(ens, strategy=strategy, backend="ref")
        entry = plan._entries["raw"]
        return entry.lower(plan.lowered, _rand_x(ens, 16)).as_text()

    small, large = program_text(16), program_text(512)
    assert len(large) < 1.1 * len(small), (len(small), len(large))


def test_deferred_prepare_pads_on_first_predict():
    # prepare=False (mesh servers): no model prep at build, one-time
    # prep on first local predict, same results.
    ens = _rand_ensemble()
    x = _rand_x(ens, 16)
    plan = Predictor.build(ens, PredictConfig(strategy="fused",
                                              backend="pallas"),
                           expected_batch=16, prepare=False)
    assert plan.stats["build_model_pads"] == 0
    got = np.asarray(plan.raw(x))
    assert plan.stats["build_model_pads"] > 0
    want = np.asarray(ref.fused_predict(x, ens.borders, ens.split_features,
                                        ens.split_bins, ens.leaf_values))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_staged_prepadded_pallas_zero_model_pads():
    ens = _rand_ensemble()
    x = _rand_x(ens, 8)
    plan = Predictor.build(ens, PredictConfig(strategy="staged",
                                              backend="pallas"))
    ops.reset_pad_stats()
    plan.raw(x)
    plan.raw(x)
    assert ops.pad_stats()["model"] == 0
    assert plan.stats["traces"]["raw"] == 1


# --------------------------------------------------------------------------
# Parity with the legacy kwarg path
# --------------------------------------------------------------------------
@pytest.mark.parametrize("cfg", [
    PredictConfig(strategy="staged", backend="ref"),
    PredictConfig(strategy="fused", backend="ref"),
    PredictConfig(strategy="staged", backend="pallas"),
    PredictConfig(strategy="fused", backend="pallas"),
    PredictConfig(strategy="staged", backend="ref", tree_block=4),
    PredictConfig(strategy="staged", backend="pallas", tree_block=4),
])
def test_plan_matches_kwarg_path_on_unpadded_ensemble(cfg):
    # 13 trees / depth 4 / 11 features: nothing divides the kernel block
    # multiples, so the prepadded plan must reproduce the per-call
    # padding exactly.
    ens = _rand_ensemble()
    x = _rand_x(ens, 37)
    want = np.asarray(ref.fused_predict(x, ens.borders, ens.split_features,
                                        ens.split_bins, ens.leaf_values))
    plan = Predictor.build(ens, cfg, expected_batch=37)
    got = np.asarray(plan.raw(x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    legacy = np.asarray(predict.raw_predict(
        ens, x, strategy=cfg.strategy, backend=cfg.backend,
        tree_block=cfg.tree_block))
    np.testing.assert_allclose(got, legacy, rtol=1e-5, atol=1e-4)


def test_proba_and_classify_match_legacy():
    ens = _rand_ensemble(n_outputs=3)
    x = _rand_x(ens, 20)
    plan = Predictor.build(ens, strategy="staged", backend="ref")
    np.testing.assert_allclose(
        np.asarray(plan.proba(x)),
        np.asarray(predict.predict_proba(ens, x, strategy="staged",
                                         backend="ref")),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(plan.classify(x)),
        np.asarray(predict.predict_class(ens, x, strategy="staged",
                                         backend="ref")))
    # binary model probas are two-column sigmoid
    bin_ens = _rand_ensemble(seed=5, n_outputs=1)
    bplan = Predictor.build(bin_ens, strategy="staged", backend="ref")
    proba = np.asarray(bplan.proba(_rand_x(bin_ens, 9)))
    assert proba.shape == (9, 2)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, rtol=1e-6)


# --------------------------------------------------------------------------
# CatBoost JSON ingestion
# --------------------------------------------------------------------------
def _catboost_json(tmp_path):
    model = {
        "features_info": {"float_features": [
            {"flat_feature_index": 0, "borders": [0.0, 1.0]},
            {"flat_feature_index": 1, "borders": [0.5]},
        ]},
        "oblivious_trees": [
            {"splits": [
                {"split_type": "FloatFeature", "float_feature_index": 0,
                 "border": 1.0},
                {"split_type": "FloatFeature", "float_feature_index": 1,
                 "border": 0.5},
            ], "leaf_values": [1.0, 2.0, 3.0, 4.0]},
            # shallower tree: importer pads it to the ensemble depth
            {"splits": [
                {"split_type": "FloatFeature", "float_feature_index": 0,
                 "border": 0.0},
            ], "leaf_values": [10.0, 20.0]},
        ],
        "scale_and_bias": [2.0, [0.25]],
    }
    path = tmp_path / "model.json"
    path.write_text(json.dumps(model))
    return path


def _hand_built_equivalent():
    """The same model assembled directly — the round-trip oracle."""
    borders = jnp.asarray(np.array([[0.0, 0.5], [1.0, np.inf]], np.float32))
    sf = jnp.asarray(np.array([[0, 1], [0, 0]], np.int32))
    sb = jnp.asarray(np.array([[2, 1], [1, PAD_SPLIT_BIN]], np.int32))
    lv = jnp.asarray(2.0 * np.array(
        [[[1.0], [2.0], [3.0], [4.0]],
         [[10.0], [20.0], [0.0], [0.0]]], np.float32))
    return ObliviousEnsemble(sf, sb, lv, borders,
                             jnp.asarray(np.array([2, 1], np.int32)),
                             base_score=jnp.asarray([0.25], jnp.float32))


def test_catboost_json_roundtrip_matches_hand_built(tmp_path):
    ens = load_catboost_json(_catboost_json(tmp_path))
    want = _hand_built_equivalent()
    assert ens.describe() == want.describe()
    np.testing.assert_array_equal(np.asarray(ens.split_features),
                                  np.asarray(want.split_features))
    np.testing.assert_array_equal(np.asarray(ens.split_bins),
                                  np.asarray(want.split_bins))
    np.testing.assert_allclose(np.asarray(ens.leaf_values),
                               np.asarray(want.leaf_values))
    np.testing.assert_allclose(np.asarray(ens.base_score), [0.25])

    x = jnp.asarray(np.array([[-1.0, 0.0], [0.5, 0.9], [2.0, 0.9],
                              [2.0, 0.0]], np.float32))
    plan = Predictor.from_catboost_json(_catboost_json(tmp_path),
                                        PredictConfig(strategy="fused",
                                                      backend="ref"))
    got = np.asarray(plan.raw(x))[:, 0]
    # hand computation: raw = 2*(tree0_leaf + tree1_leaf) + 0.25 where
    # tree0 leaf bit0 = x0 > 1.0, bit1 = x1 > 0.5; tree1 bit0 = x0 > 0.0
    expect = np.array([2 * (1 + 10), 2 * (3 + 20), 2 * (4 + 20),
                       2 * (2 + 20)], np.float32) + 0.25
    np.testing.assert_allclose(got, expect, rtol=1e-6)
    np.testing.assert_allclose(
        got, np.asarray(predict.raw_predict(
            ens, x, strategy="staged", backend="ref"))[:, 0], rtol=1e-6)


def test_catboost_json_multiclass_scale_and_bias(tmp_path):
    """Multiclass export edge: per-class bias vector + scale applied to
    every leaf value; 3-class leaf tables are leaf-major."""
    model = {
        "features_info": {"float_features": [
            {"flat_feature_index": 0, "borders": [0.0]},
        ]},
        "oblivious_trees": [
            {"splits": [
                {"split_type": "FloatFeature", "float_feature_index": 0,
                 "border": 0.0},
            ],
             # leaf-major: leaf 0 -> classes (1, 2, 3), leaf 1 -> (4, 5, 6)
             "leaf_values": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]},
        ],
        "scale_and_bias": [0.5, [0.1, 0.2, 0.3]],
    }
    path = tmp_path / "mc.json"
    path.write_text(json.dumps(model))
    ens = load_catboost_json(path)
    assert ens.n_outputs == 3
    np.testing.assert_allclose(np.asarray(ens.base_score), [0.1, 0.2, 0.3])
    np.testing.assert_allclose(np.asarray(ens.leaf_values[0, 0]),
                               [0.5, 1.0, 1.5])
    plan = Predictor.build(ens, strategy="staged", backend="ref")
    x = jnp.asarray([[-1.0], [1.0]], jnp.float32)
    np.testing.assert_allclose(
        np.asarray(plan.raw(x)),
        np.asarray([[0.6, 1.2, 1.8], [2.1, 2.7, 3.3]]), rtol=1e-6)
    # mismatched bias width is a hard error
    model["scale_and_bias"] = [1.0, [0.1, 0.2]]
    path.write_text(json.dumps(model))
    with pytest.raises(ValueError, match="scale_and_bias"):
        load_catboost_json(path)


def _nonuniform_json(tmp_path):
    """Depths 3 / 1 / 2: exercises true_depths + depth_grouped."""
    b = {"split_type": "FloatFeature", "float_feature_index": 0}
    model = {
        "features_info": {"float_features": [
            {"flat_feature_index": 0, "borders": [0.0, 1.0, 2.0]},
        ]},
        "oblivious_trees": [
            {"splits": [dict(b, border=0.0), dict(b, border=1.0),
                        dict(b, border=2.0)],
             "leaf_values": [float(v) for v in range(8)]},
            {"splits": [dict(b, border=1.0)],
             "leaf_values": [10.0, 20.0]},
            {"splits": [dict(b, border=0.0), dict(b, border=2.0)],
             "leaf_values": [1.0, 2.0, 3.0, 4.0]},
        ],
    }
    path = tmp_path / "mixed.json"
    path.write_text(json.dumps(model))
    return path


def test_catboost_json_nonuniform_depths_lower_grouped(tmp_path):
    ens = load_catboost_json(_nonuniform_json(tmp_path))
    assert ens.depth == 3
    np.testing.assert_array_equal(ens.true_depths, [3, 1, 2])
    # shallow trees use the PAD_SPLIT_BIN always-left convention: the
    # padded levels can never fire, so their leaf-index bits stay 0
    sb = np.asarray(ens.split_bins)
    assert sb[1, 1] == PAD_SPLIT_BIN and sb[1, 2] == PAD_SPLIT_BIN
    assert sb[2, 2] == PAD_SPLIT_BIN
    np.testing.assert_array_equal(np.asarray(ens.leaf_values)[1, 2:, 0], 0.0)
    # an auto plan on this model picks depth_grouped and matches ref
    from repro.kernels import ref
    plan = Predictor.build(ens, PredictConfig(strategy="staged",
                                              backend="ref"))
    assert plan.config.layout == "depth_grouped"
    lowered = plan.describe()["lowered"]
    assert lowered["groups"] == {1: 1, 2: 1, 3: 1}
    x = jnp.asarray([[-0.5], [0.5], [1.5], [2.5]], jnp.float32)
    want = np.asarray(ref.fused_predict(
        x, ens.borders, ens.split_features, ens.split_bins,
        ens.leaf_values))
    np.testing.assert_allclose(np.asarray(plan.raw(x)), want,
                               rtol=1e-6, atol=1e-6)
    # hand check: x=2.5 crosses every border -> tree0 leaf 7,
    # tree1 leaf 1, tree2 leaf 3
    np.testing.assert_allclose(np.asarray(plan.raw(x))[3, 0],
                               7.0 + 20.0 + 4.0, rtol=1e-6)


def test_catboost_json_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"oblivious_trees": []}))
    with pytest.raises(ValueError):
        load_catboost_json(bad)
    # border value that is not among the feature's borders
    model = json.loads(_catboost_json(tmp_path).read_text())
    model["oblivious_trees"][0]["splits"][0]["border"] = 0.33
    bad.write_text(json.dumps(model))
    with pytest.raises(ValueError, match="border"):
        load_catboost_json(bad)


# --------------------------------------------------------------------------
# Ensemble concat/slice validation
# --------------------------------------------------------------------------
def test_concat_validates_compatibility():
    a = _rand_ensemble(seed=1)
    ok = concat_ensembles(a, a)           # same borders: fine
    assert ok.n_trees == 2 * a.n_trees
    with pytest.raises(ValueError, match="depth"):
        concat_ensembles(a, _rand_ensemble(seed=1, depth=3))
    with pytest.raises(ValueError, match="n_outputs"):
        concat_ensembles(a, _rand_ensemble(seed=1, n_outputs=5))
    with pytest.raises(ValueError, match="border"):
        concat_ensembles(a, _rand_ensemble(seed=2, n_borders=7))
    with pytest.raises(ValueError, match="border"):
        concat_ensembles(a, _rand_ensemble(seed=99))  # same shape, new vals


def test_slice_trees_validates_range():
    a = _rand_ensemble()
    assert a.slice_trees(0, 5).n_trees == 5
    for start, stop in ((-1, 4), (4, 2), (0, a.n_trees + 1)):
        with pytest.raises(ValueError):
            a.slice_trees(start, stop)
