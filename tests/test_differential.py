"""Differential kernel-testing harness.

Every (op x impl x layout x bin-dtype) cell of the kernel registry's
capability table is enumerated AT COLLECTION TIME from
`registry.table()` — not hand-listed — and asserted against the
pure-jnp ref oracle on randomized ensembles/batches.  A newly
registered implementation (or a new layout/dtype claim on an existing
one) is covered here with zero new test code.

Two scenarios fold in the classic edge cases:
  mixed   mixed true depths including a depth-0 tree, NaN features,
          batch size not divisible by the 32-doc lane width
  edge    255 borders (bin ids at the 0/255 uint8 edges), T=1,
          batch=1, feature values below every border / above every
          border / NaN
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import layout as layout_mod
from repro.core import trees
from repro.core.trees import ObliviousEnsemble
from repro.kernels import histogram as hist_k
from repro.kernels import ops, ref, registry

# One pytest param per capability-table cell.  New registrations expand
# this list automatically at collection time.
CELLS = [
    pytest.param(row["op"], row["impl"], lay, dt,
                 id=f"{row['op']}-{row['impl']}-{lay}-{dt}")
    for row in registry.table()
    for lay in row["layouts"].split("/")
    for dt in row["dtypes"].split("/")
]

INT_DTYPES = ("int32", "uint8")


def _scenario(name):
    """Build (ensemble, x) for one named scenario."""
    if name == "mixed":
        rng = np.random.default_rng(11)
        n, f, b, t, d, c = 21, 7, 9, 6, 4, 2
        x = rng.normal(size=(n, f)).astype(np.float32)
        x[rng.random((n, f)) < 0.08] = np.nan
        borders = np.sort(rng.normal(size=(b, f)), 0).astype(np.float32)
        sf = rng.integers(0, f, (t, d)).astype(np.int32)
        sb = rng.integers(1, b + 1, (t, d)).astype(np.int32)
        lv = rng.normal(size=(t, 1 << d, c)).astype(np.float32)
        ens = ObliviousEnsemble(jnp.asarray(sf), jnp.asarray(sb),
                                jnp.asarray(lv), jnp.asarray(borders),
                                jnp.full((f,), b, jnp.int32))
        ens = trees.truncate_tree_depths(ens,
                                         np.array([0, 1, 2, 4, 3, 4]))
    else:  # "edge"
        rng = np.random.default_rng(23)
        f, b, t, d, c = 3, 255, 1, 2, 1
        borders = np.sort(rng.normal(size=(b, f)), 0).astype(np.float32)
        # one row: below every border (bin 0), above every border
        # (bin 255 — the uint8 ceiling), NaN (bin 0 by contract)
        x = np.array([[borders[0, 0] - 1.0, borders[-1, 1] + 1.0,
                       np.nan]], np.float32)
        sf = np.array([[1, 0]], np.int32)
        sb = np.array([[255, 1]], np.int32)
        lv = rng.normal(size=(t, 1 << d, c)).astype(np.float32)
        ens = ObliviousEnsemble(jnp.asarray(sf), jnp.asarray(sb),
                                jnp.asarray(lv), jnp.asarray(borders),
                                jnp.full((f,), b, jnp.int32))
    return ens, jnp.asarray(x)


def _bins(x, borders, dtype):
    if dtype == "uint8":
        return ref.binarize_u8(x, borders)
    return ref.binarize(x, borders)


def _family(impl):
    return "pallas" if impl.startswith("pallas") else "ref"


def _assert_int_equal(got, want):
    np.testing.assert_array_equal(np.asarray(got).astype(np.int64),
                                  np.asarray(want).astype(np.int64))


def _assert_close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("scenario", ["mixed", "edge"])
@pytest.mark.parametrize("op,impl,lay,dtype", CELLS)
def test_cell_matches_ref_oracle(op, impl, lay, dtype, scenario):
    ens, x = _scenario(scenario)
    sf, sb, lv = ens.split_features, ens.split_bins, ens.leaf_values
    borders = ens.borders
    fn = registry.get(op, impl).fn

    if op == "binarize":
        got = fn(x, borders)
        _assert_int_equal(got, _bins(x, borders, dtype))
        return

    if op == "l2sq":
        a = jnp.nan_to_num(x)
        _assert_close(fn(a, borders), ref.l2sq_matrix(a, borders))
        _assert_close(fn(a[0], borders), ref.l2sq_rowwise(a[0], borders))
        return

    bins = _bins(x, borders, dtype if dtype in INT_DTYPES else "int32")
    want_idx = ref.leaf_index(bins, sf, sb)

    if op == "leaf_index":
        if lay in ("soa", "depth_grouped"):
            _assert_int_equal(fn(bins, sf, sb), want_idx)
        elif lay == "depth_major":
            low = layout_mod.lower(ens, "depth_major",
                                   backend=_family(impl))
            binsp = ops.pad_features(bins, low.onehot.shape[2])
            got = fn(binsp, low.onehot, low.split_bins_dm, low.pow2)
            _assert_int_equal(got[:, :ens.n_trees], want_idx)
            # padded trees must land in leaf 0
            _assert_int_equal(got[:, ens.n_trees:],
                              np.zeros_like(got[:, ens.n_trees:]))
        else:  # bitpacked: bit-exact vs the soa oracle, by contract
            got = fn(bins, jnp.transpose(sf), jnp.transpose(sb))
            _assert_int_equal(got, want_idx)
        return

    if op == "node_index":
        # the oblivious trees rewritten as node tables: leaf ids keep
        # the oblivious index, so the soa oracle's ids are the answer
        low = layout_mod.lower(ens, "nodes", backend=_family(impl))
        binsp = ops.pad_features(bins, low.borders.shape[1])
        got = fn(binsp, low.node_features, low.node_bins, low.node_left,
                 low.node_right, low.paths, low.path_len, width=low.width)
        _assert_int_equal(got[:, :ens.n_trees], want_idx)
        # padded trees must land in leaf 0
        _assert_int_equal(got[:, ens.n_trees:],
                          np.zeros_like(got[:, ens.n_trees:]))
        return

    if op == "leaf_gather":
        _assert_close(fn(want_idx, lv), ref.leaf_gather(want_idx, lv))
        return

    if op == "histogram":
        # feature-major bins (the training stream), random leaf ids and
        # g/h stats vs the segment-sum oracle.  The mixed scenario's
        # NaN features land in bin 0 by contract; the edge scenario
        # covers bin ids at the 0/255 uint8 edges and batch=1.
        # n_leaves=1 is the single-leaf (depth-0 level) case.
        rng = np.random.default_rng(31)
        n = int(bins.shape[0])
        n_bins = int(borders.shape[0]) + 1
        bins_t = jnp.transpose(bins)
        for n_leaves in (1, 4):
            leaf = jnp.asarray(rng.integers(0, n_leaves, n)
                               .astype(np.int32))
            g = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
            got = fn(bins_t, leaf, g, n_bins=n_bins, n_leaves=n_leaves)
            want = hist_k.histogram_ref(bins_t, leaf, g, n_bins=n_bins,
                                        n_leaves=n_leaves)
            _assert_close(got, want)
        return

    assert op == "fused_predict", f"harness does not cover op {op!r}"
    want = ref.fused_predict(x, borders, sf, sb, lv)
    if lay in ("soa", "depth_grouped"):
        got = fn(x, borders, sf, sb, lv)
    elif lay == "depth_major":
        low = layout_mod.lower(ens, "depth_major", backend=_family(impl))
        got = fn(x, low.borders, low.onehot, low.split_bins_dm, low.pow2,
                 low.leaf_values, prepadded=True, n_classes=ens.n_outputs)
    else:  # bitpacked
        got = fn(x, borders, jnp.transpose(sf), jnp.transpose(sb), lv)
    _assert_close(got, want)


def test_table_covers_every_core_op():
    """The harness is only exhaustive if the table is: every core op
    must contribute at least one cell, and the bitpacked layout must
    appear for both structure-consuming ops."""
    ops_seen = {c.values[0] for c in CELLS}
    assert set(registry.CORE_OPS) <= ops_seen
    bp = {(c.values[0]) for c in CELLS if c.values[2] == "bitpacked"}
    assert {"leaf_index", "fused_predict"} <= bp


def test_train_on_pool_matches_train_on_float():
    """The quantized-first trainer (uint8 pool, registered histogram
    kernels) must reproduce the seed float-path scan to the leaf-value
    level: identical split structure, identical leaf values, identical
    loss trajectory — and perform zero binarize dispatches while
    boosting."""
    from repro.core import boosting
    from repro.core.losses import make_loss

    rng = np.random.default_rng(5)
    x = rng.normal(size=(400, 6)).astype(np.float32)
    y = (x[:, 0] - 2.0 * x[:, 2] + 0.3 * rng.normal(size=400)
         ).astype(np.float32)
    loss = make_loss("rmse")
    params = boosting.BoostingParams(n_trees=8, depth=3, max_bins=16,
                                     seed=3)
    ens_f, hist_f = boosting.fit_scan(x, y, loss=loss, params=params)
    ens_p, hist_p = boosting.fit(x, y, loss=loss, params=params)

    _assert_int_equal(ens_p.split_features, ens_f.split_features)
    _assert_int_equal(ens_p.split_bins, ens_f.split_bins)
    np.testing.assert_allclose(np.asarray(ens_p.leaf_values),
                               np.asarray(ens_f.leaf_values),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(hist_p["train_loss"], hist_f["train_loss"],
                               rtol=0, atol=1e-6)
    assert hist_p["dispatch_delta"].get("binarize", 0) == 0
    assert hist_p["dispatch_delta"].get("histogram", 0) > 0


def test_histogram_additive_across_row_chunks():
    """Property: histograms are additive over row chunks — summing the
    per-chunk histograms equals the full-batch histogram (the invariant
    chunked/streamed training relies on)."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="hypothesis not installed")
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=25)
    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 64),
           f=st.integers(1, 5), n_bins=st.integers(1, 9),
           n_leaves=st.integers(1, 4), frac=st.floats(0.0, 1.0))
    def prop(seed, n, f, n_bins, n_leaves, frac):
        rng = np.random.default_rng(seed)
        bins_t = jnp.asarray(rng.integers(0, n_bins, (f, n))
                             .astype(np.int32))
        leaf = jnp.asarray(rng.integers(0, n_leaves, n).astype(np.int32))
        g = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
        full = hist_k.histogram_ref(bins_t, leaf, g, n_bins=n_bins,
                                    n_leaves=n_leaves)
        k = int(round(frac * n))
        parts = sum(
            hist_k.histogram_ref(bins_t[:, lo:hi], leaf[lo:hi], g[lo:hi],
                                 n_bins=n_bins, n_leaves=n_leaves)
            for lo, hi in ((0, k), (k, n)) if hi > lo)
        _assert_close(parts, full)

    prop()
