"""The plain reference for non-symmetric trees, and its control.

`raw_f64` walks every tree from its root in float64 numpy, straight
from the node arrays: bin = #{borders < x}, then node = right if
bin >= node_bins else left until a leaf (a child < 0 is leaf -1 - c),
and sums the leaves.  It imports nothing of the program.  Rows go in
blocks so that 10,000 trees fit in host memory.

`control_raw` is the same walk with the leaf values rounded to
bfloat16 and the sum in float32 (`reference.control_raw`'s step below
the configuration's float32).  It must come out as not correct.
"""
from __future__ import annotations

import numpy as np

BLOCK_ROWS = 64


def leaf_index(model: dict, x: np.ndarray) -> np.ndarray:
    """(n, T) int64 leaf ids, by walking from each root."""
    borders = np.asarray(model["borders"], np.float64)
    bins = (np.asarray(x, np.float64)[:, None, :] > borders[None]).sum(1)
    nf, nb = model["node_features"], model["node_bins"]
    left, right = model["node_left"], model["node_right"]
    rows = np.arange(bins.shape[0])[:, None]
    trees = np.arange(nf.shape[0])[None, :]
    node = np.zeros((bins.shape[0], nf.shape[0]), np.int64)
    while (node >= 0).any():
        at = np.maximum(node, 0)
        go = bins[rows, nf[trees, at]] >= nb[trees, at]
        child = np.where(go, right[trees, at], left[trees, at])
        node = np.where(node >= 0, child, node)
    return -1 - node


def _tree_sum(model: dict, x: np.ndarray, leaves: np.ndarray,
              dtype) -> np.ndarray:
    t = np.arange(leaves.shape[0])[None, :]
    out = np.empty((x.shape[0], leaves.shape[2]), dtype)
    for i in range(0, x.shape[0], BLOCK_ROWS):
        idx = leaf_index(model, x[i:i + BLOCK_ROWS])
        out[i:i + BLOCK_ROWS] = leaves[t, idx].sum(1, dtype=dtype)
    return out + np.asarray(model["base_score"], dtype)[None]


def raw_f64(model: dict, x: np.ndarray) -> np.ndarray:
    return _tree_sum(model, x, np.asarray(model["leaf_values"], np.float64),
                     np.float64)


def control_raw(model: dict, x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    lv = np.asarray(model["leaf_values"], np.float32)
    lv = lv.astype(ml_dtypes.bfloat16).astype(np.float32)
    return _tree_sum(model, x, lv, np.float32)
