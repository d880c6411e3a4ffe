"""Lowered ensemble layouts (`core.layout`): lowering correctness and
parity across soa / depth_major / depth_grouped, registry layout
routing, tuning-based layout selection, plan integration (config
resolution, stats, serving metrics), and the lowered-pytree round
trips."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import layout as layout_mod
from repro.core.layout import (BitpackedLayout, DepthGroupedLayout,
                               DepthMajorLayout, NodesLayout, SoaLayout,
                               lower)
from repro.core.predictor import PredictConfig, Predictor
from repro.core.trees import (ObliviousEnsemble, PAD_SPLIT_BIN,
                              truncate_tree_depths)
from repro.kernels import ops, ref, registry, tuning


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


def _mixed_depth(ens, cycle=(1, 2, 3, None)):
    """Truncate tree t to depth cycle[t % len] through the canonical
    `trees.truncate_tree_depths` (trailing always-left pads)."""
    depths = [ens.depth if cycle[t % len(cycle)] is None
              else min(cycle[t % len(cycle)], ens.depth)
              for t in range(ens.n_trees)]
    return truncate_tree_depths(ens, depths)


def _rand_x(ens, n=37, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, ens.n_features))
                       .astype(np.float32))


def _want(ens, x):
    return np.asarray(ens.base_score)[None, :] + np.asarray(
        ref.fused_predict(x, ens.borders, ens.split_features,
                          ens.split_bins, ens.leaf_values))


# --------------------------------------------------------------------------
# true_depths metadata
# --------------------------------------------------------------------------
def test_true_depths():
    ens = _mixed_depth(_rand_ensemble(n_trees=8, depth=4))
    np.testing.assert_array_equal(ens.true_depths,
                                  [1, 2, 3, 4, 1, 2, 3, 4])
    # uniform ensembles report the shared depth everywhere
    uni = _rand_ensemble(n_trees=5)
    np.testing.assert_array_equal(uni.true_depths, [4] * 5)
    # a PAD level BETWEEN real levels is not depth padding
    sb = np.asarray(uni.split_bins).copy()
    sb[0, 1] = PAD_SPLIT_BIN           # mid-level pad: still depth 4
    sb[1, 1:] = PAD_SPLIT_BIN          # trailing run: depth 1
    mixed = dataclasses.replace(uni, split_bins=jnp.asarray(sb))
    np.testing.assert_array_equal(mixed.true_depths, [4, 1, 4, 4, 4])


def test_true_depths_all_padded_tree():
    ens = _rand_ensemble(n_trees=3)
    sb = np.asarray(ens.split_bins).copy()
    sb[1, :] = PAD_SPLIT_BIN           # depth-0 (constant) tree
    ens = dataclasses.replace(ens, split_bins=jnp.asarray(sb))
    np.testing.assert_array_equal(ens.true_depths, [4, 0, 4])
    # lowering clamps the group to depth 1 and stays correct
    x = _rand_x(ens, 9)
    plan = Predictor.build(ens, PredictConfig(
        strategy="staged", backend="ref", layout="depth_grouped"))
    np.testing.assert_allclose(np.asarray(plan.raw(x)), _want(ens, x),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# Lowering parity: every layout == the logical model, on unpadded,
# depth-padded, and mixed-depth ensembles, both kernel families,
# both strategies
# --------------------------------------------------------------------------
@pytest.mark.parametrize("layout", layout_mod.LAYOUT_NAMES)
@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("strategy", ["staged", "fused"])
def test_layout_parity_mixed_depth(layout, backend, strategy):
    ens = _mixed_depth(_rand_ensemble())
    x = _rand_x(ens, 37)
    plan = Predictor.build(ens, PredictConfig(
        strategy=strategy, backend=backend, layout=layout),
        expected_batch=37)
    np.testing.assert_allclose(np.asarray(plan.raw(x)), _want(ens, x),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("layout", layout_mod.LAYOUT_NAMES)
def test_layout_parity_unpadded_uniform(layout):
    ens = _rand_ensemble()                 # no padded levels at all
    x = _rand_x(ens, 21)
    plan = Predictor.build(ens, PredictConfig(
        strategy="staged", backend="ref", layout=layout))
    np.testing.assert_allclose(np.asarray(plan.raw(x)), _want(ens, x),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("layout", layout_mod.LAYOUT_NAMES)
def test_layout_parity_quantized_pool(layout):
    # the pool path starts at leaf_sum: every layout must score a
    # pre-quantized uint8 pool identically to its float path
    ens = _mixed_depth(_rand_ensemble())
    x = _rand_x(ens, 19)
    plan = Predictor.build(ens, PredictConfig(
        strategy="staged", backend="ref", layout=layout))
    pool = plan.quantize(x)
    np.testing.assert_array_equal(np.asarray(plan.raw(pool)),
                                  np.asarray(plan.raw(x)))


@pytest.mark.parametrize("layout", layout_mod.LAYOUT_NAMES)
@pytest.mark.parametrize("strategy", ["staged", "fused"])
def test_layout_parity_padded_classes(layout, strategy):
    # 9 classes pad to 16 in the lowered table: the pallas plans must
    # sum the padded classes to nothing and never return them
    ens = _mixed_depth(_rand_ensemble(n_outputs=9))
    x = _rand_x(ens, 37)
    plan = Predictor.build(ens, PredictConfig(
        strategy=strategy, backend="pallas", layout=layout),
        expected_batch=37)
    got = np.asarray(plan.raw(x))
    assert got.shape == (37, 9)
    np.testing.assert_allclose(got, _want(ens, x), rtol=1e-5, atol=1e-4)


def _leaf_tables(low, ens, tree_block):
    """Each lowered leaf table beside the model-format (T, L, C) rows it
    must hold."""
    lv = np.asarray(ens.leaf_values)
    if isinstance(low, (DepthGroupedLayout, BitpackedLayout)):
        depths = np.maximum(np.asarray(ens.true_depths), 1)
        return [(g.leaf_values, lv[depths == g.depth][:, :1 << g.depth])
                for g in low.groups]
    if tree_block:
        return [(blk[2], lv[i * tree_block:(i + 1) * tree_block])
                for i, blk in enumerate(low.tree_blocks)]
    return [(low.leaf_values, lv)]


@pytest.mark.parametrize("n_outputs", [1, 7, 8, 9])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("layout,tree_block", [
    ("soa", 0), ("soa", 4), ("depth_major", 0), ("depth_grouped", 0),
    ("bitpacked", 0)])
def test_lowered_leaf_table_is_class_major(layout, tree_block, backend,
                                           n_outputs):
    # every layout holds its leaf tables as (Tp, round_up(C, 8), L):
    # the model's values transposed, zero in padded classes and trees
    ens = _mixed_depth(_rand_ensemble(n_outputs=n_outputs))
    low = lower(ens, layout, backend=backend, t_align=16,
                tree_block=tree_block)
    cp = -(-n_outputs // 8) * 8
    tables = _leaf_tables(low, ens, tree_block)
    for table, model in tables:
        table = np.asarray(table)
        n, n_leaves, _ = model.shape
        tp = table.shape[0]
        assert table.shape == (tp, cp, n_leaves)
        if backend == "pallas":
            assert tp % (128 if layout == "bitpacked" else 16) == 0
        else:
            assert tp == n
        np.testing.assert_array_equal(table[:n, :n_outputs],
                                      model.transpose(0, 2, 1))
        assert not table[:, n_outputs:].any()
        assert not table[n:].any()
    assert sum(t.shape[0] for t, _ in tables) >= ens.n_trees
    assert low.leaf_table_bytes() == sum(np.asarray(t).nbytes
                                         for t, _ in tables)


def test_depth_major_ref_is_bit_exact():
    # the one-hot matmul touches only f32-exact integers: depth_major
    # on the jnp reference must be BIT-identical to soa, not just close
    ens = _mixed_depth(_rand_ensemble())
    x = _rand_x(ens, 33)
    soa = Predictor.build(ens, PredictConfig(
        strategy="staged", backend="ref", layout="soa"))
    dm = Predictor.build(ens, PredictConfig(
        strategy="staged", backend="ref", layout="depth_major"))
    np.testing.assert_array_equal(np.asarray(soa.raw(x)),
                                  np.asarray(dm.raw(x)))


def test_depth_grouped_shrinks_leaf_tables():
    ens = _mixed_depth(_rand_ensemble(n_trees=16, depth=5))
    soa = lower(ens, "soa")
    grouped = lower(ens, "depth_grouped")
    assert grouped.leaf_table_bytes() < soa.leaf_table_bytes() / 2
    # group structure: one group per distinct clamped depth, all trees
    assert [g.depth for g in grouped.groups] == [1, 2, 3, 5]
    assert sum(g.n_trees for g in grouped.groups) == ens.n_trees


def test_lower_pallas_pads_model_once():
    ens = _rand_ensemble()
    ops.reset_pad_stats()
    low = lower(ens, "depth_major", backend="pallas", t_align=16)
    assert ops.pad_stats()["model"] == low.n_model_pads > 0
    assert low.borders.shape[1] % ops.FEATURE_ALIGN == 0
    assert low.onehot.shape[0] % 16 == 0
    assert low.split_bins_dm.shape == (ens.depth, low.onehot.shape[0])
    # ref lowering keeps exact shapes (padding would be wasted math)
    assert lower(ens, "depth_major").onehot.shape[0] == ens.n_trees


def test_lower_rejects_unknown_layout_and_tracers():
    ens = _rand_ensemble()
    with pytest.raises(ValueError, match="unknown layout"):
        lower(ens, "warp")
    # depth_grouped must refuse tracer ensembles (shard-local plans)
    def build_traced(sb):
        traced = dataclasses.replace(ens, split_bins=sb)
        return lower(traced, "depth_grouped")
    with pytest.raises(Exception):
        jax.eval_shape(build_traced, ens.split_bins)


# --------------------------------------------------------------------------
# Registry layout routing + capability metadata
# --------------------------------------------------------------------------
def test_registry_layout_resolution():
    assert registry.resolve("leaf_index", "ref",
                            layout="depth_major") == "ref_dm"
    assert registry.resolve("leaf_index", "pallas",
                            layout="depth_major") == "pallas_dm"
    # soa kernels serve depth_grouped directly (per-group evaluation)
    assert registry.resolve("leaf_index", "ref",
                            layout="depth_grouped") == "ref"
    # binarize is layout-independent
    assert registry.resolve("binarize", "ref",
                            layout="depth_major") == "ref"
    # uint8 pools route to the shared dm impl (it takes both dtypes)
    assert registry.resolve("leaf_index", "ref", dtype="uint8",
                            layout="depth_major") == "ref_dm"
    # bitpacked routes via the _bp suffix exactly like _dm
    assert registry.resolve("leaf_index", "ref",
                            layout="bitpacked") == "ref_bp"
    assert registry.resolve("leaf_index", "pallas", dtype="uint8",
                            layout="bitpacked") == "pallas_bp"
    assert registry.resolve("fused_predict", "pallas",
                            layout="bitpacked") == "pallas_bp"
    assert registry.resolve("binarize", "ref", layout="bitpacked") == "ref"
    with pytest.raises(ValueError, match="does not consume"):
        registry.resolve("leaf_gather", "ref", layout="nope")


def test_every_layout_claims_only_covered_ops():
    # the CI capability smoke, as a test: every op a layout claims has
    # at least one registered implementation consuming that layout
    for name, spec in layout_mod.LAYOUTS.items():
        for op in spec.claimed_ops:
            impls = registry.impls_for_layout(op, name)
            assert impls, f"layout {name} claims {op} with no impl"
    rows = registry.table()
    assert all("layouts" in r for r in rows)
    assert "layouts" in registry.format_table().splitlines()[0]


# --------------------------------------------------------------------------
# Tuning: layout selection from ensemble shape
# --------------------------------------------------------------------------
def test_best_layout_heuristics():
    mixed = np.tile([2, 3, 4, 6], 25)
    uniform = np.full(100, 6)
    assert tuning.best_layout(mixed, 1, 54) == "depth_grouped"
    assert tuning.best_layout(mixed, 1, 54,
                              backend="pallas") == "depth_grouped"
    # uniform depths: the hoisted one-hot pays off only for the pallas
    # kernel family; the jnp reference gathers cheaper than it matmuls
    assert tuning.best_layout(uniform, 1, 54) == "soa"
    assert tuning.best_layout(uniform, 1, 54,
                              backend="pallas") == "depth_major"
    # a one-hot matrix over budget falls back to soa
    assert tuning.best_layout(np.full(200_000, 8), 1, 512,
                              backend="pallas") == "soa"
    assert tuning.best_layout(np.asarray([], np.int64), 1, 54) == "soa"
    # mixed depths whose f32 one-hot working set blows the VMEM budget
    # route to the integer bitpacked pipeline (any backend)
    huge_mixed = np.tile([4, 6, 8, 10], 50_000)
    assert tuning.best_layout(huge_mixed, 1, 512) == "bitpacked"
    assert tuning.best_layout(huge_mixed, 1, 512,
                              backend="pallas") == "bitpacked"
    costs = tuning.layout_costs(mixed, 1, 54)
    assert costs["depth_grouped_leaf_bytes"] < costs["soa_leaf_bytes"]
    assert costs["bitpacked_leaf_bytes"] == costs["depth_grouped_leaf_bytes"]
    assert 0 < costs["bitpacked_plane_bytes"] < costs["soa_leaf_bytes"]


# --------------------------------------------------------------------------
# Plan integration
# --------------------------------------------------------------------------
def test_config_layout_validation_and_resolution():
    with pytest.raises(ValueError, match="layout"):
        PredictConfig(layout="columnar")
    with pytest.raises(ValueError, match="soa-layout"):
        PredictConfig(tree_block=4, layout="depth_grouped")
    ens = _mixed_depth(_rand_ensemble())
    r = PredictConfig().resolve(ens)
    assert r.layout == "depth_grouped"      # mixed depths -> grouped
    assert r.is_resolved
    # tree blocking pins auto to soa (blocked loop is an soa feature)
    rb = PredictConfig(tree_block=4).resolve(ens)
    assert rb.layout == "soa"
    # uniform-depth ref plans stay on the compatibility default
    assert PredictConfig().resolve(_rand_ensemble()).layout == "soa"
    assert not PredictConfig(layout="depth_major").is_resolved


def test_plan_stats_expose_layout_and_lowering():
    ens = _mixed_depth(_rand_ensemble())
    plan = Predictor.build(ens, PredictConfig(strategy="staged",
                                              backend="ref"))
    s = plan.stats
    assert s["layout"] == "depth_grouped"
    assert s["lower_time_s"] >= 0.0
    assert plan.describe()["layout"] == "depth_grouped"
    assert plan.describe()["lowered"]["layout"] == "depth_grouped"
    assert "depth_grouped" in repr(plan)
    # deferred prepare (mesh-style plans): lowering lands on first call
    lazy = Predictor.build(ens, PredictConfig(strategy="staged",
                                              backend="ref"),
                           prepare=False)
    assert lazy.stats["lower_time_s"] == 0.0
    lazy.raw(_rand_x(ens, 5))
    assert lazy._lowered is not None


def test_server_metrics_report_layout():
    from repro.serving.engine import GBDTServer
    ens = _mixed_depth(_rand_ensemble(n_outputs=1))
    server = GBDTServer(ens, config=PredictConfig(strategy="staged",
                                                  backend="ref"),
                        max_batch=16)
    try:
        snap = server.metrics.snapshot()
        assert snap["layout"] == "depth_grouped"
    finally:
        server.close()


# --------------------------------------------------------------------------
# Lowered layouts are well-behaved pytrees
# --------------------------------------------------------------------------
@pytest.mark.parametrize("layout", layout_mod.LAYOUT_NAMES)
def test_lowered_pytree_roundtrip(layout):
    ens = _mixed_depth(_rand_ensemble())
    low = lower(ens, layout)
    leaves, td = jax.tree_util.tree_flatten(low)
    back = jax.tree_util.tree_unflatten(td, leaves)
    assert type(back) is type(low)
    x = _rand_x(ens, 7)
    bins = ref.binarize(x, ens.borders)
    np.testing.assert_array_equal(
        np.asarray(low.leaf_sum(bins, backend="ref", block_t=16)),
        np.asarray(back.leaf_sum(bins, backend="ref", block_t=16)))
    # structural maps must not re-run lowering logic
    nones = jax.tree_util.tree_map(lambda _: None, low,
                                   is_leaf=lambda v: v is None)
    assert isinstance(nones, (SoaLayout, DepthMajorLayout,
                              DepthGroupedLayout, BitpackedLayout,
                              NodesLayout))
