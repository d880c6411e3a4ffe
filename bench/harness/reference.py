"""The plain reference and its control.

`raw_f64` is the oblivious-tree sum in float64, straight from the model
arrays: bin = #{borders < x}, leaf index = sum_d 2^d [bin >= split],
sum of the leaves.  It imports nothing of the program.  Rows go in
blocks so that 10,000 trees x 7 classes fit in host memory.

`control_raw` is the same sum with the leaf values rounded to
bfloat16, what one bfloat16 MXU pass makes of a float32 contraction
(products exact, the sum in float32): the step below the float32 the
configurations state.  It must come out as not correct.
"""
from __future__ import annotations

import numpy as np

BLOCK_ROWS = 64


def leaf_index(model: dict, x: np.ndarray) -> np.ndarray:
    """(n, T) int64 leaf ids."""
    borders = np.asarray(model["borders"], np.float64)
    sf, sb = model["split_features"], model["split_bins"]
    bins = (np.asarray(x, np.float64)[:, None, :] > borders[None]).sum(1)
    go = bins[:, sf] >= sb[None]                            # (n, T, D)
    return (go * (1 << np.arange(sf.shape[1]))).sum(-1)


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


def proba(raw: np.ndarray) -> np.ndarray:
    """Class probabilities: two-column sigmoid for one output, softmax
    otherwise (the output the program's `proba` entries return)."""
    raw = np.asarray(raw, np.float64)
    if raw.shape[1] == 1:
        p = 1.0 / (1.0 + np.exp(-raw[:, 0]))
        return np.stack([1.0 - p, p], axis=1)
    e = np.exp(raw - raw.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def max_abs_err(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)))


def bad_rows(proba_rows: np.ndarray, tol: float = 1e-3) -> int:
    """Rows that are no probability vector (unwritten, NaN, or not
    summing to 1): a whole-output check beside the sampled comparison."""
    p = np.asarray(proba_rows, np.float64)
    ok = np.isfinite(p).all(1) & (np.abs(p.sum(1) - 1.0) <= tol) \
        & (p >= 0).all(1)
    return int((~ok).sum())
