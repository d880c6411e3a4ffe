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
from repro.core.trees import (NodeEnsemble, ObliviousEnsemble,
                              PAD_SPLIT_BIN, truncate_tree_depths)
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


def _want64(ens, x):
    """float64 numpy: bin each row, walk each tree's depths, sum."""
    x = np.asarray(x, np.float64)
    bins = (x[:, None, :] > np.asarray(ens.borders, np.float64)[None]
            ).sum(1)                                        # (N, F)
    sf, sb = np.asarray(ens.split_features), np.asarray(ens.split_bins)
    right = bins[:, sf] >= sb[None]                         # (N, T, D)
    leaf = (right << np.arange(sf.shape[1])).sum(-1)        # (N, T)
    lv = np.asarray(ens.leaf_values, np.float64)
    vals = lv[np.arange(sf.shape[0])[None, :], leaf]        # (N, T, C)
    return np.asarray(ens.base_score, np.float64)[None] + vals.sum(1)


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
@pytest.mark.parametrize("layout,tree_block", [
    *((name, 0) for name in layout_mod.LAYOUT_NAMES), ("soa", 4)])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("strategy", ["staged", "fused"])
def test_layout_parity_mixed_depth(layout, tree_block, backend, strategy):
    # 13 trees: no layout's packed leaf table holds a whole number of
    # groups, and tree_block=4 blocks cut on group boundaries
    ens = _mixed_depth(_rand_ensemble())
    x = _rand_x(ens, 37)
    plan = Predictor.build(ens, PredictConfig(
        strategy=strategy, backend=backend, layout=layout,
        tree_block=tree_block), expected_batch=37)
    np.testing.assert_allclose(np.asarray(plan.raw(x)), _want64(ens, x),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("layout", layout_mod.LAYOUT_NAMES)
@pytest.mark.parametrize("backend,strategy", [("ref", "staged"),
                                              ("pallas", "fused")])
def test_layout_parity_unpadded_uniform(layout, backend, strategy):
    # one depth, so the fused plans run their own kernels: fused_predict
    # (soa), fused_predict_dm (depth_major), fused_predict_bp (one
    # bitpacked group)
    ens = _rand_ensemble()                 # no padded levels at all
    x = _rand_x(ens, 21)
    plan = Predictor.build(ens, PredictConfig(
        strategy=strategy, backend=backend, layout=layout))
    np.testing.assert_allclose(np.asarray(plan.raw(x)), _want64(ens, x),
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
        # blocks are cut on group boundaries of the packed table
        step = -(-tree_block // tuning.trees_per_step(lv.shape[1])) \
            * tuning.trees_per_step(lv.shape[1])
        return [(blk[2], lv[i * step:(i + 1) * step])
                for i, blk in enumerate(low.tree_blocks)]
    return [(low.leaf_values, lv)]


@pytest.mark.parametrize("n_outputs", [1, 7, 8, 9])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("layout,tree_block", [
    ("soa", 0), ("soa", 4), ("depth_major", 0), ("depth_grouped", 0),
    ("bitpacked", 0), ("nodes", 0)])
def test_lowered_leaf_table_is_class_major(layout, tree_block, backend,
                                           n_outputs):
    # every layout holds its leaf tables packed class-major, (Tp/G,
    # round_up(C, 8), G * Lp): G trees side by side on lanes, tree t's
    # leaves at lanes (t % G) * Lp + l of row t // G, zero in padded
    # classes, leaf slots and trees; ops.leaf_major inverts it bit for
    # bit
    ens = _mixed_depth(_rand_ensemble(n_outputs=n_outputs))
    low = lower(ens, layout, backend=backend, t_align=16,
                tree_block=tree_block)
    cp = -(-n_outputs // 8) * 8
    if layout == "nodes":
        # the oblivious trees rewritten as node tables, leaves in W slots
        lv = np.asarray(NodeEnsemble.from_oblivious(ens).leaf_values)
        model = np.zeros((lv.shape[0], low.width, n_outputs), np.float32)
        model[:, :lv.shape[1]] = lv
        tables = [(low.leaf_values, model)]
    else:
        tables = _leaf_tables(low, ens, tree_block)
    for table, model in tables:
        table = np.asarray(table)
        n, n_leaves, _ = model.shape
        g = tuning.trees_per_step(n_leaves)
        lp = -(-n_leaves // 8) * 8
        rows = table.shape[0]
        assert table.shape == (rows, cp, g * lp)
        if backend == "pallas":
            assert rows * g % (128 if layout == "bitpacked" else 16) == 0
            assert rows * g >= n
        else:
            assert rows == -(-n // g)
        unpacked = (table.reshape(rows, cp, g, lp).transpose(0, 2, 3, 1)
                    .reshape(rows * g, lp, cp))
        np.testing.assert_array_equal(unpacked[:n, :n_leaves, :n_outputs],
                                      model)
        assert not unpacked[:, :, n_outputs:].any()
        assert not unpacked[:, n_leaves:].any()
        assert not unpacked[n:].any()
        back = ops.leaf_major(jnp.asarray(table), n_outputs, rows * g)
        assert back.shape == (rows * g, lp, n_outputs)
        np.testing.assert_array_equal(np.asarray(back),
                                      unpacked[:, :, :n_outputs])
        np.testing.assert_array_equal(
            np.asarray(ops.class_major(back, rows * g)), table)
        np.testing.assert_array_equal(
            np.asarray(ops.class_major(jnp.asarray(model), rows * g)),
            table)
    assert sum(t.shape[0] * tuning.trees_per_step(m.shape[1])
               for t, m in tables) >= ens.n_trees
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


@pytest.mark.parametrize("n_trees", [16, 256])
def test_depth_grouped_shrinks_leaf_tables(n_trees):
    # each depth group's packed table pads its trees to whole groups of
    # up to 64: at 16 mixed-depth trees the groups are mostly padding,
    # bigger than soa's one table, and best_layout keeps away from
    # depth_grouped; at 256 the groups fill and the saving is real
    ens = _mixed_depth(_rand_ensemble(n_trees=n_trees, depth=5))
    soa = lower(ens, "soa")
    grouped = lower(ens, "depth_grouped")
    costs = tuning.layout_costs(ens.true_depths, ens.n_outputs,
                                ens.n_features)
    assert costs["soa_leaf_bytes"] == soa.leaf_table_bytes()
    assert costs["depth_grouped_leaf_bytes"] == grouped.leaf_table_bytes()
    picked = tuning.best_layout(ens.true_depths, ens.n_outputs,
                                ens.n_features)
    if n_trees == 16:
        assert grouped.leaf_table_bytes() > soa.leaf_table_bytes()
        assert picked != "depth_grouped"
    else:
        assert grouped.leaf_table_bytes() < soa.leaf_table_bytes() / 2
        assert picked == "depth_grouped"
    # group structure: one group per distinct clamped depth, all trees,
    # each padded to whole groups of its packed table
    depths = np.maximum(np.asarray(ens.true_depths), 1)
    assert [g.depth for g in grouped.groups] == [1, 2, 3, 5]
    for g in grouped.groups:
        step = tuning.trees_per_step(1 << g.depth)
        assert g.n_trees == -(-(depths == g.depth).sum() // step) * step


def test_lower_pallas_pads_model_once():
    ens = _rand_ensemble()
    ops.reset_pad_stats()
    low = lower(ens, "depth_major", backend="pallas", t_align=16)
    assert ops.pad_stats()["model"] == low.n_model_pads > 0
    assert low.borders.shape[1] % ops.FEATURE_ALIGN == 0
    assert low.onehot.shape[0] % 16 == 0
    assert low.split_bins_dm.shape == (ens.depth, low.onehot.shape[0])
    # ref lowering pads only the tree axis, to whole groups of the
    # packed leaf table (any more would be wasted math)
    ref_low = lower(ens, "depth_major")
    step = tuning.trees_per_step(1 << ens.depth)
    assert ref_low.onehot.shape[0] == -(-ens.n_trees // step) * step
    assert ref_low.borders.shape == ens.borders.shape


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
    ens = _mixed_depth(_rand_ensemble(n_trees=256))
    r = PredictConfig().resolve(ens)
    assert r.layout == "depth_grouped"      # mixed depths -> grouped
    # a few mixed-depth trees: each depth group's packed leaf table
    # would be mostly padding, so auto stays on soa
    assert PredictConfig().resolve(
        _mixed_depth(_rand_ensemble())).layout == "soa"
    assert r.is_resolved
    # tree blocking pins auto to soa (blocked loop is an soa feature)
    rb = PredictConfig(tree_block=4).resolve(ens)
    assert rb.layout == "soa"
    # uniform-depth ref plans stay on the compatibility default
    assert PredictConfig().resolve(_rand_ensemble()).layout == "soa"
    assert not PredictConfig(layout="depth_major").is_resolved


def test_plan_stats_expose_layout_and_lowering():
    ens = _mixed_depth(_rand_ensemble(n_trees=256))
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


@pytest.mark.parametrize("layout", layout_mod.LAYOUT_NAMES)
@pytest.mark.parametrize("depth", [1, 5, 8])
def test_describe_reports_trees_per_step(layout, depth):
    # G comes from each leaf table's leaf count alone (L = 2^d, W for
    # node tables), and the leaf sum runs Tp/G steps a row block
    ens = _rand_ensemble(n_trees=70, depth=depth)
    if layout in ("depth_grouped", "bitpacked"):
        ens = _mixed_depth(ens)
    plan = Predictor.build(ens, PredictConfig(
        strategy="staged", backend="ref", layout=layout))
    low = plan.lowered
    d = plan.describe()["lowered"]
    if layout in ("depth_grouped", "bitpacked"):
        tables = [(g.leaf_values, 1 << g.depth) for g in low.groups]
    elif layout == "nodes":
        tables = [(low.leaf_values, low.width)]
    else:
        tables = [(low.leaf_values, 1 << depth)]
    gs = [tuning.trees_per_step(n) for _, n in tables]
    want = gs[0] if len(set(gs)) == 1 else gs
    assert d["trees_per_step"] == want
    assert d["leaf_steps_per_block"] == sum(lv.shape[0] for lv, _ in tables)
    for (lv, n), g in zip(tables, gs):
        lp = -(-n // 8) * 8
        assert g == min(max(tuning.LEAF_STEP_LANES // lp, 1), 64)
        assert lv.shape[2] == g * lp


def test_server_metrics_report_layout():
    from repro.serving.engine import GBDTServer
    ens = _mixed_depth(_rand_ensemble(n_trees=256, n_outputs=1))
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
