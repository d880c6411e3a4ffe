"""A random node-table ensemble (non-symmetric trees) of the
configuration's shape, as CatBoost's Lossguide grow policy makes them.

The arrays follow the program's `NodeEnsemble`:

    node_features (T, N) int32   feature node j of tree t tests
    node_bins     (T, N) int32   go right iff bin >= node_bins
    node_left     (T, N) int32   child: node id >= 0, or leaf l as -1 - l
    node_right    (T, N) int32
    leaf_values   (T, L, C)      float32
    borders, n_borders, base_score as in `model.py`

Every tree is grown by max_leaves - 1 splits, node 0 first: each split
turns a leaf drawn uniformly from those above the depth limit into a
node, whose left child keeps the leaf's id and whose right child is the
next leaf id.  The borders are `model.compute_borders` over a seeded
subsample, as for the oblivious configurations.
"""
from __future__ import annotations

import numpy as np

from harness import data
from harness.model import compute_borders


def random_node_ensemble(config: dict, x: np.ndarray, seed: int) -> dict:
    """The model's arrays as numpy, keyed as `NodeEnsemble`'s fields."""
    spec = config["model"]
    r = data.rng(seed, data.STREAM_MODEL)
    sub = min(int(spec["border_sample_rows"]), x.shape[0])
    rows = np.sort(r.choice(x.shape[0], size=sub, replace=False))
    borders, nb = compute_borders(x[rows], int(spec["borders"]))
    t, c = int(spec["trees"]), int(spec["outputs"])
    n_leaves, max_depth = int(spec["max_leaves"]), int(spec["depth"])
    n_nodes = n_leaves - 1
    trees = np.arange(t)
    left = np.zeros((t, n_nodes), np.int32)
    right = np.zeros((t, n_nodes), np.int32)
    depth = np.zeros((t, n_leaves), np.int64)
    # where each leaf hangs: (node, side 0 left / 1 right); -1 the root
    parent = np.full((t, n_leaves), -1, np.int64)
    side = np.zeros((t, n_leaves), np.int64)
    for j in range(n_nodes):
        open_ = depth[:, :j + 1] < max_depth
        pick = r.random(t) * open_.sum(1)
        leaf = np.argmax(np.cumsum(open_, 1) > pick[:, None], axis=1)
        hung = parent[trees, leaf]
        for arr, s in ((left, 0), (right, 1)):
            at = (hung >= 0) & (side[trees, leaf] == s)
            arr[trees[at], hung[at]] = j
        left[:, j], right[:, j] = -1 - leaf, -1 - (j + 1)
        depth[trees, leaf] += 1
        depth[:, j + 1] = depth[trees, leaf]
        parent[trees, leaf], side[trees, leaf] = j, 0
        parent[:, j + 1], side[:, j + 1] = j, 1
    nf = r.integers(0, x.shape[1], (t, n_nodes)).astype(np.int32)
    nbins = (1 + r.integers(0, 1 << 30, (t, n_nodes))
             % np.maximum(nb[nf], 1)).astype(np.int32)
    lv = r.standard_normal((t, n_leaves, c), dtype=np.float32)
    lv *= np.float32(spec["leaf_scale"])
    return {"node_features": nf, "node_bins": nbins, "node_left": left,
            "node_right": right, "leaf_values": lv, "borders": borders,
            "n_borders": nb, "base_score": np.zeros((c,), np.float32)}


def to_program(model: dict):
    """The program's `NodeEnsemble` over the same arrays."""
    import jax.numpy as jnp

    from repro.core.trees import NodeEnsemble

    return NodeEnsemble(**{k: jnp.asarray(v) for k, v in model.items()})
