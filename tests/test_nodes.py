"""Non-symmetric trees (`trees.NodeEnsemble`, the `nodes` layout and the
`node_index` kernel): the Pallas path match equals the reference walk
bit for bit, and every scoring surface (plan entries over floats and
pools, `BulkScorer`, `GBDTServer`) matches a float64 numpy walk."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import layout as layout_mod
from repro.core.predictor import PredictConfig, Predictor
from repro.core.trees import NodeEnsemble, ObliviousEnsemble
from repro.kernels import ops, ref, registry

F, B, MAX_LEAVES, MAX_DEPTH = 20, 30, 31, 8


def _grow(rng, n_leaves, max_depth):
    """One Lossguide-shaped tree: split a leaf drawn uniformly from those
    above the depth limit, n_leaves - 1 times.  Leaf ids: the left child
    keeps its parent's id, the right child takes the next one."""
    nodes = []                          # [feature, bin, left, right]
    leaves = {0: (None, 0)}             # leaf id -> (parent slot, depth)
    for _ in range(n_leaves - 1):
        open_ = [l for l, (_, d) in leaves.items() if d < max_depth]
        leaf = open_[rng.integers(len(open_))]
        where, depth = leaves.pop(leaf)
        j, new = len(nodes), len(nodes) + 1
        nodes.append([int(rng.integers(F)), int(rng.integers(1, B + 1)),
                      -1 - leaf, -1 - new])
        if where is not None:
            nodes[where[0]][where[1]] = j
        leaves[leaf] = ((j, 2), depth + 1)
        leaves[new] = ((j, 3), depth + 1)
    return nodes


def _chain(rng, depth):
    """A depth-`depth` chain: node j goes left to leaf j, right to node
    j + 1; the last node's right child is leaf `depth`."""
    return [[int(rng.integers(F)), int(rng.integers(1, B + 1)), -1 - j,
             j + 1 if j + 1 < depth else -1 - depth] for j in range(depth)]


def _model(seed=0, n_trees=64, n_outputs=3):
    """A seeded node table as numpy arrays: a depth-8 chain, a
    single-leaf tree, and Lossguide trees of 2 to 31 leaves."""
    rng = np.random.default_rng(seed)
    trees = [_chain(rng, MAX_DEPTH), []]
    trees += [_grow(rng, int(rng.integers(2, MAX_LEAVES + 1)), MAX_DEPTH)
              for _ in range(n_trees - 2)]
    trees[-1] = _grow(rng, MAX_LEAVES, MAX_DEPTH)
    n_nodes = MAX_LEAVES - 1
    arr = np.zeros((4, n_trees, n_nodes), np.int32)
    arr[2:] = -1                       # a single-leaf tree: both to leaf 0
    for t, nodes in enumerate(trees):
        if nodes:
            arr[:, t, :len(nodes)] = np.asarray(nodes, np.int32).T
    x = rng.normal(size=(512, F)).astype(np.float32)
    borders = np.sort(rng.normal(size=(B, F)), 0).astype(np.float32)
    return {"node_features": arr[0], "node_bins": arr[1],
            "node_left": arr[2], "node_right": arr[3],
            "leaf_values": rng.normal(
                scale=0.1, size=(n_trees, MAX_LEAVES, n_outputs))
            .astype(np.float32),
            "borders": borders, "n_borders": np.full((F,), B, np.int32),
            "base_score": rng.normal(size=(n_outputs,)).astype(np.float32),
            }, x


def _ensemble(m):
    return NodeEnsemble(**{k: jnp.asarray(v) for k, v in m.items()})


def _walk_raw(m, x):
    """float64 numpy: walk every tree from the root, sum the leaves."""
    bins = (x.astype(np.float64)[:, None, :]
            > m["borders"].astype(np.float64)[None]).sum(1)
    t_ids = np.arange(m["node_features"].shape[0])[None, :]
    node = np.zeros((x.shape[0], t_ids.shape[1]), np.int64)
    while (node >= 0).any():
        at = np.maximum(node, 0)
        right = bins[np.arange(x.shape[0])[:, None],
                     m["node_features"][t_ids, at]] \
            >= m["node_bins"][t_ids, at]
        child = np.where(right, m["node_right"][t_ids, at],
                         m["node_left"][t_ids, at])
        node = np.where(node >= 0, child, node)
    leaves = m["leaf_values"].astype(np.float64)[t_ids, -1 - node]
    return leaves.sum(1) + m["base_score"].astype(np.float64)[None]


def _softmax(raw):
    if raw.shape[1] == 1:
        p = 1.0 / (1.0 + np.exp(-raw[:, 0]))
        return np.stack([1.0 - p, p], axis=1)
    e = np.exp(raw - raw.max(1, keepdims=True))
    return e / e.sum(1, keepdims=True)


def test_random_tables_cover_the_shapes():
    m, _ = _model()
    ens = _ensemble(m)
    p, plen = ens.path_matrix()
    assert p.shape == (64, MAX_LEAVES, MAX_LEAVES - 1)
    lengths, n_leaves = plen.max(1), (plen >= 0).sum(1)
    assert lengths[0] == MAX_DEPTH and n_leaves[0] == MAX_DEPTH + 1
    np.testing.assert_array_equal(p[0, MAX_DEPTH, :MAX_DEPTH], 1)
    assert n_leaves[1] == 1 and plen[1, 0] == 0 and not p[1].any()
    assert lengths.max() == ens.depth == MAX_DEPTH
    assert n_leaves[-1] == MAX_LEAVES and n_leaves[2:].min() < MAX_LEAVES
    # a path's length is its count of steps
    np.testing.assert_array_equal(np.abs(p).sum(2)[plen >= 0],
                                  plen[plen >= 0])


@pytest.mark.parametrize("dtype", ["uint8", "int32"])
def test_node_index_kernel_equals_the_walk(dtype):
    m, x = _model(seed=1)
    ens = _ensemble(m)
    low = layout_mod.lower(ens, "nodes", backend="pallas")
    assert low.width == 32 and low.node_features.shape[1] == 128
    bins = (ref.binarize_u8 if dtype == "uint8" else ref.binarize)(
        jnp.asarray(x), ens.borders)
    binsp = ops.pad_features(bins, low.borders.shape[1])
    args = (binsp, low.node_features, low.node_bins, low.node_left,
            low.node_right, low.paths, low.path_len)
    got = registry.get("node_index", "pallas").fn(*args, width=low.width)
    want = ref.node_index(bins, ens.node_features, ens.node_bins,
                          ens.node_left, ens.node_right)
    np.testing.assert_array_equal(np.asarray(got)[:, :ens.n_trees],
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got)[:, ens.n_trees:], 0)
    walked = registry.get("node_index", "ref").fn(*args, width=low.width)
    np.testing.assert_array_equal(np.asarray(walked), np.asarray(got))


@pytest.mark.parametrize("n_outputs", [1, 3, 7])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("strategy", ["staged", "fused"])
def test_plan_matches_float64_walk(n_outputs, backend, strategy):
    m, x = _model(seed=2, n_outputs=n_outputs)
    plan = Predictor.build(_ensemble(m), PredictConfig(
        strategy=strategy, backend=backend))
    assert plan.config.layout == "nodes"
    raw = _walk_raw(m, x)
    pool = plan.quantize(x)
    for got in (plan.raw(x), plan.raw(pool)):
        np.testing.assert_allclose(np.asarray(got, np.float64), raw,
                                   rtol=0, atol=1e-5)
    for got in (plan.proba(x), plan.proba(pool)):
        np.testing.assert_allclose(np.asarray(got, np.float64),
                                   _softmax(raw), rtol=0, atol=1e-5)


def test_default_config_resolves_to_nodes_and_describes_the_table():
    m, _ = _model(seed=3)
    plan = Predictor.build(_ensemble(m))
    assert plan.config.layout == "nodes"
    d = plan.describe()["lowered"]
    assert d["layout"] == "nodes"
    assert (d["nodes_per_tree"], d["leaves_per_tree"]) == (30, 31)
    assert d["lowered_bytes"] >= d["leaf_table_bytes"] > 0
    assert plan.describe()["max_leaves"] == MAX_LEAVES


def test_bulk_scorer_and_server_match_float64_walk():
    from repro.scoring import ArraySource, BulkScorer, ScoreConfig
    from repro.serving.engine import GBDTServer

    m, x = _model(seed=4, n_outputs=7)
    ens = _ensemble(m)
    want = _softmax(_walk_raw(m, x))
    plan = Predictor.build(ens, PredictConfig(backend="pallas",
                                              strategy="fused"))
    res = BulkScorer(plan, ScoreConfig(chunk_rows=256, output="proba")) \
        .score(ArraySource(x[:300]))
    np.testing.assert_allclose(np.asarray(res.output, np.float64),
                               want[:300], rtol=0, atol=1e-5)
    server = GBDTServer(ens, max_batch=64)
    try:
        assert server.predictor.config.layout == "nodes"
        got = server.predict_batch(x[:50])
        np.testing.assert_allclose(np.asarray(got, np.float64), want[:50],
                                   rtol=0, atol=1e-5)
    finally:
        server.close()


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_oblivious_as_node_table_scores_the_same(backend):
    rng = np.random.default_rng(5)
    t, d, c = 24, 4, 3
    ens = ObliviousEnsemble(
        jnp.asarray(rng.integers(0, F, (t, d)).astype(np.int32)),
        jnp.asarray(rng.integers(1, B, (t, d)).astype(np.int32)),
        jnp.asarray(rng.normal(size=(t, 1 << d, c)).astype(np.float32)),
        jnp.asarray(np.sort(rng.normal(size=(B, F)), 0).astype(np.float32)),
        jnp.full((F,), B, jnp.int32))
    x = jnp.asarray(rng.normal(size=(70, F)).astype(np.float32))
    nodes = NodeEnsemble.from_oblivious(ens)
    assert nodes.depth == d and nodes.node_features.shape == (t, 15)
    cfg = PredictConfig(strategy="staged", backend=backend)
    np.testing.assert_allclose(
        np.asarray(Predictor.build(nodes, cfg).raw(x)),
        np.asarray(Predictor.build(ens, cfg).raw(x)), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("layout", ["soa", "depth_major", "depth_grouped",
                                    "bitpacked"])
def test_oblivious_layouts_refuse_node_tables(layout):
    ens = _ensemble(_model(seed=6)[0])
    with pytest.raises(ValueError, match="NodeEnsemble"):
        Predictor.build(ens, PredictConfig(layout=layout))
    with pytest.raises(ValueError, match="NodeEnsemble"):
        layout_mod.lower(ens, layout)


def test_sharded_refuses_node_tables():
    plan = Predictor.build(_ensemble(_model(seed=7)[0]))
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="nodes layout"):
        plan.sharded(mesh)


def test_malformed_trees_are_refused():
    m, _ = _model(seed=8)
    m["node_left"][5, 0] = 0                       # the root its own child
    with pytest.raises(ValueError, match="reached twice"):
        _ensemble(m).path_matrix()
    m, _ = _model(seed=8)
    m["node_left"][0, 2] = -1                      # leaf 0 by two paths
    with pytest.raises(ValueError, match="two paths"):
        _ensemble(m).path_matrix()
