"""The float64 reference, its control, the generators and the arrival
schedule."""
import json

import numpy as np
import pytest

from harness import data, model, reference, schedule

from conftest import BENCH


def _one_tree():
    # 2 features, borders at 0.5 and 1.5 on each; one depth-2 tree that
    # tests feature 0 at bin >= 1, then feature 1 at bin >= 2
    return {"split_features": np.array([[0, 1]], np.int32),
            "split_bins": np.array([[1, 2]], np.int32),
            "leaf_values": np.array([[[1.0], [2.0], [3.0], [4.0]]],
                                    np.float32),
            "borders": np.array([[0.5, 0.5], [1.5, 1.5]], np.float32),
            "n_borders": np.array([2, 2], np.int32),
            "base_score": np.array([0.25], np.float32)}


def test_reference_by_hand():
    m = _one_tree()
    x = np.array([[0.0, 0.0],     # bins (0, 0): leaf 0
                  [1.0, 0.0],     # bins (1, 0): leaf 1
                  [0.0, 2.0],     # bins (0, 2): leaf 2
                  [2.0, 2.0],     # bins (2, 2): leaf 3
                  [0.5, 1.5]],    # on the borders: not above, bins (0, 1)
                 np.float32)
    np.testing.assert_array_equal(reference.leaf_index(m, x)[:, 0],
                                  [0, 1, 2, 3, 0])
    np.testing.assert_array_equal(reference.raw_f64(m, x)[:, 0],
                                  [1.25, 2.25, 3.25, 4.25, 1.25])


def test_proba_sigmoid_and_softmax():
    p = reference.proba(np.array([[0.0], [np.log(3.0)]]))
    np.testing.assert_allclose(p, [[0.5, 0.5], [0.25, 0.75]])
    q = reference.proba(np.array([[0.0, np.log(3.0)]]))
    np.testing.assert_allclose(q, [[0.25, 0.75]])


def test_bad_rows_and_max_err():
    good = np.array([[0.25, 0.75], [1.0, 0.0]])
    bad = np.array([[0.0, 0.0], [np.nan, 1.0], [0.6, 0.6]])
    assert reference.bad_rows(good) == 0
    assert reference.bad_rows(bad) == 3
    assert reference.max_abs_err(good, good + 1e-7) == pytest.approx(1e-7)
    assert reference.max_abs_err(bad[1:2], good[:1]) == float("inf")
    assert reference.max_abs_err(good[:1], good) == float("inf")


def _tiny(name, trees=200):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg["data"]["rows"] = 2000
    cfg["model"].update(trees=trees, border_sample_rows=1000)
    return cfg


@pytest.mark.parametrize("name", ["covertype", "santander"])
def test_program_matches_reference_and_control_does_not(name):
    """The program's plan (CPU, reference kernels) agrees with the
    float64 reference far inside the configuration's limit; the
    bfloat16 control misses it."""
    import jax.numpy as jnp

    from repro.core.predictor import Predictor

    cfg = _tiny(name, trees=2000)     # enough trees for the control to
    x, _ = data.generate(cfg, 5)      # miss by several times the limit
    m = model.random_ensemble(cfg, x, 5)
    rows = x[data.sample_rows(len(x), 256, 5)]
    want = reference.proba(reference.raw_f64(m, rows))
    got = np.asarray(Predictor.build(model.to_program(m))
                     .proba(jnp.asarray(rows)))
    limit = cfg["limits"]["proba_max_abs_err"]
    assert reference.max_abs_err(got, want) < limit / 10
    ctl = reference.proba(reference.control_raw(m, rows))
    assert reference.max_abs_err(ctl, want) > 2 * limit


def test_generators_are_seeded_and_shaped():
    cfg = _tiny("santander")
    a, ya = data.generate(cfg, 2**40 + 3)
    b, _ = data.generate(cfg, 2**40 + 3)
    c, _ = data.generate(cfg, 2**40 + 4)
    assert a.shape == (2000, 200) and a.dtype == np.float32
    assert set(np.unique(ya)) <= {0, 1}
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    with pytest.raises(ValueError):
        data.rng(-1, 0)


def test_random_ensemble_is_valid():
    cfg = _tiny("covertype", trees=50)
    x, _ = data.generate(cfg, 9)
    m = model.random_ensemble(cfg, x, 9)
    assert m["leaf_values"].shape == (50, 256, 7)
    assert m["borders"].shape == (254, 54)
    nb = m["n_borders"]
    assert (m["split_bins"] >= 1).all()
    assert (m["split_bins"] <= np.maximum(nb[m["split_features"]], 1)).all()
    # borders are sorted and +inf padded past each feature's count
    for j in range(54):
        col = m["borders"][:, j]
        assert np.all(np.diff(col[:nb[j]]) > 0)
        assert np.all(np.isinf(col[nb[j]:]))


def test_sample_rows():
    s = data.sample_rows(100, 10, 3)
    assert len(set(s)) == 10 and list(s) == sorted(s)
    np.testing.assert_array_equal(s, data.sample_rows(100, 10, 3))
    assert len(data.sample_rows(5, 10, 3)) == 5


def test_poisson_schedule_same_set_in_another_order():
    tr = {"rate_per_s": 2000, "arrivals": "poisson", "schedule_seed": 0}
    a = schedule.arrivals(tr, 3.0, 1)
    b = schedule.arrivals(tr, 3.0, 2)
    assert np.all(np.diff(a) >= 0) and a[-1] < 3.0
    assert abs(len(a) / 3.0 - 2000) < 150
    assert abs(len(a) - len(b)) < 150
    assert not np.array_equal(a[:50], b[:50])


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError, match="unknown arrivals"):
        schedule.arrivals({"rate_per_s": 1, "arrivals": "x"}, 1.0, 1)
