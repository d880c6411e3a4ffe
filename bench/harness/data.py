"""Rows and seeded random streams, from the configuration's parameters.

A copy of the class-mixture recipe of the program's synthetic
generators (`repro.data.synthetic`), kept here so that no change to the
program can move the benchmark's inputs.  One general generator reads
the configuration's "data" block:

    rows, features, classes       table shape
    informative                   share of features that carry the class
    noise                         std of the Gaussian noise
    integer_frac                  share of trailing integer-valued columns
    column_scale_lognormal        [mean, sigma]: per-column scale, as
                                  un-normalised real tables have
"""
from __future__ import annotations

import numpy as np

# Independent streams drawn from one --seed.
STREAM_DATA, STREAM_MODEL, STREAM_ORDER, STREAM_SAMPLE = 1, 2, 3, 4


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use of the run's seed; any seed >= 0 works,
    including ones wider than 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def class_mixture(spec: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(x (rows, features) float32, y (rows,) int32)."""
    r = rng(seed, STREAM_DATA)
    n, f, c = int(spec["rows"]), int(spec["features"]), int(spec["classes"])
    n_inf = max(2, int(f * spec["informative"]))
    centers = r.normal(scale=2.0, size=(c, n_inf)).astype(np.float32)
    y = r.integers(0, c, size=n).astype(np.int32)
    x = r.standard_normal((n, f), dtype=np.float32)
    x *= np.float32(spec.get("noise", 1.0))
    x[:, :n_inf] += centers[y]
    n_int = int(f * spec.get("integer_frac", 0.0))
    if n_int:
        x[:, -n_int:] = np.round(x[:, -n_int:] * 3)
    scale = spec.get("column_scale_lognormal")
    if scale:
        x *= r.lognormal(scale[0], scale[1], size=(1, f)).astype(np.float32)
    return x, y


GENERATORS = {"class_mixture": class_mixture}


def generate(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    spec = config["data"]
    return GENERATORS[spec["generator"]](spec, seed)


def sample_rows(n: int, k: int, seed: int) -> np.ndarray:
    """k distinct row ids of n, sorted, drawn from the seed."""
    k = min(k, n)
    return np.sort(rng(seed, STREAM_SAMPLE).choice(n, size=k,
                                                    replace=False))
