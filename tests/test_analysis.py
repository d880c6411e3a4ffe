"""Contract-checker tests: the full matrix must verify clean, and the
lints must actually fire — proven with deliberately-broken toy kernels
registered (and unregistered) around each test, including a fixture
that re-introduces the PR-7 histogram widening bug."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import checker, jaxpr_tools, matrix, passes, report
from repro.kernels import registry


@pytest.fixture(scope="module")
def full_report():
    """One full-matrix run shared by the clean-matrix assertions."""
    return checker.run_check()


# --------------------------------------------------------------------------
# The shipped matrix verifies clean
# --------------------------------------------------------------------------
def test_full_matrix_clean(full_report):
    r = full_report
    assert r.ok, "\n" + r.format(verbose=True)
    assert r.cells >= 70          # 6 ops x impls x layouts x dtypes
    assert r.kernels >= 40        # every pallas cell audited
    assert r.traces > 0
    # layout-identical calls collapse in the trace cache
    assert r.trace_cache_hits > 0


def test_declared_suppressions_are_exercised(full_report):
    """Every shipped suppression must match real findings: the jnp
    oracle widenings on leaf_index:ref and histogram:ref, and the
    widenings the v5e forces on the Pallas kernels that compare or
    lane-gather uint8 data (no 8-bit compare or gather on its VPU) —
    and the depth_grouped layout must be among leaf_index:ref's
    suppressed cells (the uint8 promotion audit of PR 6's layout)."""
    sup = full_report.suppressed
    assert all(f.rule == "widening" for f in sup)
    keys = {(f.op, f.impl) for f in sup}
    assert keys == {("leaf_index", "ref"), ("histogram", "ref"),
                    ("histogram", "pallas_u8"), ("leaf_index", "pallas_bp"),
                    ("fused_predict", "pallas_bp")}
    assert ("depth_grouped" in
            {f.layout for f in sup if f.op == "leaf_index"})
    assert all(f.dtype == "uint8" for f in sup)
    # the histogram kernel widens one (1, block_n) row, never the panel
    assert all(f.message.startswith("uint8 uint8[1,")
               for f in sup if (f.op, f.impl) == ("histogram", "pallas_u8"))


def test_verified_map_covers_every_impl(full_report):
    rows = registry.table()
    assert set(full_report.verified) \
        == {f"{r['op']}:{r['impl']}" for r in rows}
    for key, verdict in full_report.verified.items():
        assert verdict.startswith("ok"), (key, verdict)
    assert full_report.verified["leaf_index:ref"].startswith("ok (")
    assert full_report.verified["histogram:ref"].startswith("ok (")


def test_report_roundtrip(full_report, tmp_path):
    path = full_report.save(tmp_path / "r.json")
    loaded = report.ContractReport.load(path)
    assert loaded.verified == full_report.verified
    assert len(loaded.findings) == len(full_report.findings)
    assert loaded.ok == full_report.ok
    # deterministic artifact: a second save is byte-identical
    again = report.ContractReport.load(path).save(tmp_path / "r2.json")
    assert again.read_bytes() == path.read_bytes()


# --------------------------------------------------------------------------
# Widening lint fires on deliberately-widening toys
# --------------------------------------------------------------------------
def _narrow_check(impls):
    return checker.run_check(impls_filter=impls, include_plan=False,
                             include_tuning=False)


def test_widening_lint_fires_on_toy_kernel():
    """A uint8 leaf_index impl that widens the bins panel into a
    compare (instead of the sanctioned MXU/gather path) must be
    flagged."""
    @registry.register("leaf_index", "toy_widen", dtypes=("uint8",),
                       layouts=("soa",))
    def _toy(bins, sf, sb, **_kw):
        wide = bins.astype(jnp.int32)            # the violation
        gathered = jnp.take(wide, sf.reshape(-1), axis=1)
        go = (gathered.reshape(bins.shape[0], *sf.shape)
              >= sb[None, :, :]).astype(jnp.int32)
        return jnp.sum(go * (2 ** jnp.arange(sf.shape[1]))[None, None, :],
                       axis=2)

    try:
        r = _narrow_check({"leaf_index:toy_widen"})
        hits = [f for f in r.unsuppressed if f.rule == "widening"]
        assert hits, r.format(verbose=True)
        assert not r.ok
        assert r.verified["leaf_index:toy_widen"] == "FAIL"
    finally:
        registry.unregister("leaf_index", "toy_widen")


def test_pr7_histogram_widening_regression():
    """Re-introduce the PR-7 bug in a fixture: uint8 pool bins promoted
    to an int32 segment-id panel (`leaf * n_bins + bins.astype(i32)`)
    before the one-hot — the exact defect the widening lint exists to
    catch.  The lint must fire; the shipped pallas_u8 path (u8-vs-u8
    compare) must stay clean."""
    @registry.register("histogram", "toy_pr7", dtypes=("uint8",),
                       layouts=("soa",))
    def _toy(bins_t, leaf, g, *, n_bins, n_leaves, **_kw):
        seg = leaf[None, :] * n_bins + bins_t.astype(jnp.int32)
        onehot = (seg[:, :, None]
                  == jnp.arange(n_leaves * n_bins)[None, None, :]
                  ).astype(g.dtype)
        return jnp.einsum("fns,nc->fsc", onehot, g)

    try:
        r = _narrow_check({"histogram:toy_pr7"})
        hits = [f for f in r.unsuppressed if f.rule == "widening"]
        assert hits, r.format(verbose=True)
        assert "add" in hits[0].message
    finally:
        registry.unregister("histogram", "toy_pr7")
    clean = _narrow_check({"histogram:pallas_u8"})
    assert clean.ok, clean.format(verbose=True)


def test_int_pipeline_lint_fires_on_float_excursion():
    """A bitpacked leaf_index impl that rebuilds the index through
    floats (the MXU habit) defeats the layout's integer pipeline."""
    @registry.register("leaf_index", "toy_bp_float", dtypes=("int32",),
                       layouts=("bitpacked",))
    def _toy(bins, sf_bp, sb_bp, **_kw):
        d = sf_bp.shape[0]
        cols = jnp.stack([jnp.take(bins, sf_bp[i], axis=1)
                          for i in range(d)], axis=1)
        go = (cols >= sb_bp.T[None, :, :].swapaxes(1, 2)).astype(
            jnp.float32)                          # the violation
        idx = jnp.sum(go * (2.0 ** jnp.arange(d))[None, :, None], axis=1)
        return idx.astype(jnp.int32)

    try:
        r = _narrow_check({"leaf_index:toy_bp_float"})
        hits = [f for f in r.unsuppressed if f.rule == "int-pipeline"]
        assert hits, r.format(verbose=True)
    finally:
        registry.unregister("leaf_index", "toy_bp_float")


# --------------------------------------------------------------------------
# VMEM audit fires on an understated footprint
# --------------------------------------------------------------------------
def test_vmem_audit_fires_on_understated_footprint():
    """A pallas binarize whose kernel materializes a (bn, B, bf) f32
    panel the `binarize_footprint` model knows nothing about must trip
    the vmem-model audit."""
    from jax.experimental import pallas as pl

    @registry.register("binarize", "toy_fat", dtypes=("int32",),
                       layouts=("soa",))
    def _toy(x, borders, **_kw):
        def kernel(x_ref, b_ref, out_ref):
            xv = x_ref[...]
            bv = b_ref[...]
            fat = jnp.sin(xv[:, None, :] * bv[None, :, :])  # (bn, B, bf)
            out_ref[...] = jnp.sum(fat > 0.0, axis=1).astype(jnp.int32)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
            interpret=True)(x, borders)

    try:
        r = _narrow_check({"binarize:toy_fat"})
        hits = [f for f in r.unsuppressed if f.rule == "vmem-model"]
        assert hits, r.format(verbose=True)
        assert "mis-plan" in hits[0].message
    finally:
        registry.unregister("binarize", "toy_fat")


def test_vmem_audit_counts_tile_padding():
    """A kernel whose extra temporary is tiny in logical bytes but
    lane-padded on the chip — a (bn, bf, 2) f32 panel, the shape of the
    old histogram's 2-wide stats lane — must trip the vmem-model audit:
    the audit prices every buffer tile-padded, as the footprint models
    and Mosaic do.  Priced at logical bytes it would pass unnoticed."""
    from jax.experimental import pallas as pl
    from repro.kernels import tuning

    @registry.register("binarize", "toy_lane2", dtypes=("int32",),
                       layouts=("soa",))
    def _toy(x, borders, **_kw):
        def kernel(x_ref, b_ref, out_ref):
            xv = x_ref[...]
            pair = jnp.stack([xv > b_ref[0:1, :], xv > b_ref[1:2, :]],
                             axis=-1)                       # (bn, bf, 2)
            out_ref[...] = jnp.sum(pair.astype(jnp.int32), axis=-1)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
            interpret=True)(x, borders)

    try:
        r = _narrow_check({"binarize:toy_lane2"})
        hits = [f for f in r.unsuppressed if f.rule == "vmem-model"]
        assert hits, r.format(verbose=True)
        closed = matrix.trace_cell(
            matrix.Cell("binarize", "toy_lane2", "soa", "int32"))[0]
        call = jaxpr_tools.find_pallas_calls(closed.jaxpr)[0]
        refs = jaxpr_tools.pallas_ref_avals(call)
        logical = sum(jaxpr_tools.aval_bytes(a) for a in refs) \
            + jaxpr_tools.peak_live_bytes(
                jaxpr_tools.pallas_kernel_jaxpr(call), include_invars=False)
        model = tuning.binarize_footprint(*refs[0].shape,
                                          refs[1].shape[0])
        assert logical <= passes.VMEM_SLACK * model
    finally:
        registry.unregister("binarize", "toy_lane2")
    # the padding itself: lanes to 128, sublanes to 32-bit-word tiles
    assert tuning.tile_bytes((5, 2), 4) == 8 * 128 * 4
    assert tuning.tile_bytes((3, 1, 200), 1) == 3 * 32 * 256
    assert tuning.tile_bytes((40, 130), 2) == 48 * 256 * 2


# --------------------------------------------------------------------------
# Suppressions: honored, and flagged when stale
# --------------------------------------------------------------------------
def test_suppression_demotes_finding():
    @registry.register("leaf_index", "toy_sup", dtypes=("uint8",),
                       layouts=("soa",),
                       suppressions=("widening: test fixture",))
    def _toy(bins, sf, sb, **_kw):
        wide = bins.astype(jnp.int32)
        gathered = jnp.take(wide, sf.reshape(-1), axis=1)
        go = gathered.reshape(bins.shape[0], *sf.shape) >= sb[None]
        return jnp.sum(go.astype(jnp.int32), axis=2)

    try:
        r = _narrow_check({"leaf_index:toy_sup"})
        assert r.ok, r.format(verbose=True)
        assert len(r.suppressed) >= 1
        assert r.verified["leaf_index:toy_sup"].startswith("ok (")
    finally:
        registry.unregister("leaf_index", "toy_sup")


def test_unused_suppression_is_flagged():
    @registry.register("leaf_gather", "toy_stale", dtypes=("int32",),
                       layouts=("soa",),
                       suppressions=("widening: no longer needed",))
    def _toy(idx, lv, **_kw):
        return jnp.take_along_axis(
            lv, idx.T[:, :, None], axis=1).sum(axis=0)

    try:
        # narrowed runs skip the stale check by default...
        r = checker.run_check(impls_filter={"leaf_gather:toy_stale"},
                              include_plan=False, include_tuning=False)
        assert not [f for f in r.findings
                    if f.rule == "unused-suppression"]
        # ...and flag it when asked explicitly
        r = checker.run_check(impls_filter={"leaf_gather:toy_stale"},
                              include_plan=False, include_tuning=False,
                              check_unused=True)
        stale = [f for f in r.unsuppressed
                 if f.rule == "unused-suppression"]
        assert stale, r.format(verbose=True)
        assert not r.ok
    finally:
        registry.unregister("leaf_gather", "toy_stale")


def test_unknown_suppression_rule_rejected():
    with pytest.raises(ValueError, match="unknown suppression rule"):
        report.parse_suppressions(("not-a-rule: whatever",))


# --------------------------------------------------------------------------
# Trace cache + plan walk
# --------------------------------------------------------------------------
def test_trace_cache_no_retrace():
    cell = matrix.Cell("binarize", "ref", "soa", "int32")
    matrix.trace_cell(cell)
    before = matrix.cache_stats()
    matrix.trace_cell(cell)
    after = matrix.cache_stats()
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]


def test_plan_walk_never_compiles_and_caches():
    from repro.core.predictor import Predictor
    ens, _ = matrix.canonical_ensemble(n_features=8, n_trees=4)
    plan = Predictor.build(ens, strategy="staged")
    entries = plan.trace_entries(batch_sizes=(4, 8))
    assert "raw@4" in entries and "raw_pool@8" in entries
    stats = plan.stats
    assert stats["total_traces"] == 0          # nothing compiled
    misses = stats["abstract_trace_misses"]
    assert misses == len(entries)
    plan.trace_entries(batch_sizes=(4, 8))     # second walk: all cached
    assert plan.stats["abstract_trace_misses"] == misses
    for name, closed in entries.items():
        assert not passes.entry_findings(name, closed)


def test_entry_lints_fire_on_bad_avals():
    """The retrace lint must flag weak/x64 boundary avals."""
    def weak(x):
        return x + 1                            # weak-typed scalar const
    closed = jax.make_jaxpr(weak)(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    # interior weak scalars are fine — no finding
    assert not [f for f in passes.entry_findings("t", closed)
                if f.rule == "retrace"]
    closed = jax.make_jaxpr(lambda x: x * 2.0)(1.0)  # weak boundary
    assert [f for f in passes.entry_findings("t", closed)
            if f.rule == "retrace"]


# --------------------------------------------------------------------------
# Registry surface
# --------------------------------------------------------------------------
def test_format_table_has_verified_column():
    txt = registry.format_table({"binarize:ref": "ok"})
    header = txt.splitlines()[0]
    assert "verified" in header and "layouts" in header
    row = next(line for line in txt.splitlines()
               if "| binarize" in line and "| ref " in line)
    assert "| ok " in row
    blank = registry.format_table({})
    assert "| - " in blank


def test_unregister_unknown_raises():
    with pytest.raises(KeyError):
        registry.unregister("binarize", "nope")


# --------------------------------------------------------------------------
# Estimator structural pins (fail loudly on a jax upgrade)
# --------------------------------------------------------------------------
def test_pallas_refs_carry_block_shapes():
    from repro.kernels import registry as reg
    cell = matrix.Cell("fused_predict", "pallas", "soa", "uint8")
    closed = matrix.trace_cell(cell)[0]
    calls = jaxpr_tools.find_pallas_calls(closed.jaxpr)
    assert len(calls) == 1
    refs = jaxpr_tools.pallas_ref_avals(calls[0])
    assert len(refs) == 8            # 5 inputs + out + index + bins scratch
    assert np.dtype(refs[-1].dtype) == np.uint8   # u8 scratch picked
    assert all(hasattr(a, "shape") for a in refs)
    assert jaxpr_tools.peak_live_bytes(
        jaxpr_tools.pallas_kernel_jaxpr(calls[0]),
        include_invars=False) > 0
