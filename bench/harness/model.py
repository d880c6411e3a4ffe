"""A random oblivious-tree ensemble of the configuration's shape.

The arrays follow CatBoost's oblivious trees, as the program reads them:

    split_features (T, D) int32   feature tested at depth d of tree t
    split_bins     (T, D) int32   go right iff bin >= split_bin
    leaf_values    (T, 2^D, C)    float32; leaf index = sum_d 2^d right_d
    borders        (B, F) float32 quantile borders, +inf padded
    n_borders      (F,)   int32

The model is made on the host from the seed (its borders from a seeded
subsample of the rows), so the reference and the program read the very
same numbers.
"""
from __future__ import annotations

import numpy as np

from harness import data


def compute_borders(x: np.ndarray, n_borders: int
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Per-feature quantile borders: (B, F) float32 padded with +inf,
    and the true count per feature.  A border at or above a column's
    maximum separates nothing and is dropped."""
    n, f = x.shape
    qs = np.linspace(0.0, 1.0, n_borders + 2)[1:-1]
    borders = np.full((n_borders, f), np.inf, np.float32)
    counts = np.zeros((f,), np.int32)
    for j in range(f):
        col = x[:, j]
        hi = col.max()
        if col.min() == hi:
            continue
        uniq = np.unique(np.quantile(col, qs).astype(np.float32))
        uniq = uniq[uniq < hi]
        counts[j] = len(uniq)
        borders[:len(uniq), j] = uniq
    return borders, counts


def random_ensemble(config: dict, x: np.ndarray, seed: int) -> dict:
    """The model's arrays as numpy, keyed as `ObliviousEnsemble`'s fields."""
    spec = config["model"]
    r = data.rng(seed, data.STREAM_MODEL)
    sub = min(int(spec["border_sample_rows"]), x.shape[0])
    rows = np.sort(r.choice(x.shape[0], size=sub, replace=False))
    borders, nb = compute_borders(x[rows], int(spec["borders"]))
    t, d, c = int(spec["trees"]), int(spec["depth"]), int(spec["outputs"])
    sf = r.integers(0, x.shape[1], (t, d)).astype(np.int32)
    sb = (1 + r.integers(0, 1 << 30, (t, d))
          % np.maximum(nb[sf], 1)).astype(np.int32)
    lv = r.standard_normal((t, 1 << d, c), dtype=np.float32)
    lv *= np.float32(spec["leaf_scale"])
    return {"split_features": sf, "split_bins": sb, "leaf_values": lv,
            "borders": borders, "n_borders": nb,
            "base_score": np.zeros((c,), np.float32)}


def to_program(model: dict):
    """The program's `ObliviousEnsemble` over the same arrays."""
    import jax.numpy as jnp

    from repro.core.trees import ObliviousEnsemble

    return ObliviousEnsemble(**{k: jnp.asarray(v) for k, v in model.items()})
