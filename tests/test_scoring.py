"""Bulk scoring subsystem: sources/sinks/scorer parity and contracts
(<= 2 compiled chunk shapes, O(chunk) streaming memory, resume by
chunk index, multi-model quantize-once fan-out), the chunked quantize
helpers, Prefetcher error propagation, and the shared metrics
reservoir."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import quantize
from repro.core.predictor import PredictConfig, Predictor
from repro.core.quantize import QuantizedPool, quantize_pool
from repro.core.trees import ObliviousEnsemble
from repro.data.pipeline import Prefetcher
from repro.kernels import registry, tuning
from repro.scoring import (ArraySink, ArraySource, BulkScorer,
                           NpyMemmapSource, NpySink, ScoreConfig,
                           ScoringMetrics, StatsSink, SyntheticSource,
                           TopKSink, iter_chunks, plan_chunks)
from repro.serving.metrics import PercentileReservoir, ServerMetrics


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


def _rand_x(ens, n=37, seed=0):
    rng = np.random.default_rng(seed)
    return np.asarray(rng.normal(size=(n, ens.n_features)), np.float32)


def _plan(ens, **kw):
    kw.setdefault("strategy", "staged")
    kw.setdefault("backend", "ref")
    return Predictor.build(ens, PredictConfig(**kw))


# --------------------------------------------------------------------------
# Prefetcher error propagation (satellite regression)
# --------------------------------------------------------------------------
def test_prefetcher_reraises_source_exception():
    def bad_iter():
        yield 1
        yield 2
        raise RuntimeError("disk on fire")

    pf = Prefetcher(bad_iter(), depth=2)
    got = []
    with pytest.raises(RuntimeError, match="disk on fire"):
        for item in pf:
            got.append(item)
    # everything produced before the error was delivered, nothing eaten
    assert got == [1, 2]


def test_prefetcher_reraises_transform_exception():
    pf = Prefetcher(iter(range(5)), depth=2,
                    transform=lambda i: 1 // (i - 2))
    with pytest.raises(ZeroDivisionError):
        list(pf)


def test_prefetcher_normal_stream_and_order():
    pf = Prefetcher(iter(range(7)), depth=2, transform=lambda i: i * i)
    assert list(pf) == [i * i for i in range(7)]


# --------------------------------------------------------------------------
# Chunked quantization helpers (satellite)
# --------------------------------------------------------------------------
def test_quantize_pool_chunked_matches_full_matrix():
    ens = _rand_ensemble()
    x = _rand_x(ens, 103)
    full = quantize_pool(jnp.asarray(x), ens.borders)
    chunked = quantize.quantize_pool_chunked(
        (x[s:s + 16] for s in range(0, len(x), 16)), ens.borders)
    assert chunked.fingerprint == full.fingerprint
    np.testing.assert_array_equal(np.asarray(chunked.bins),
                                  np.asarray(full.bins))


def test_quantize_pool_chunked_never_sees_full_matrix():
    """The memory contract: only O(chunk) float rows in flight."""
    ens = _rand_ensemble()
    x = _rand_x(ens, 200)
    seen = []

    def watched():
        for s in range(0, len(x), 32):
            chunk = x[s:s + 32]
            seen.append(len(chunk))
            yield chunk

    pool = quantize.quantize_pool_chunked(watched(), ens.borders)
    assert pool.n_rows == 200
    assert max(seen) <= 32               # never a dataset-sized slab


def test_quantize_pool_chunked_validates():
    ens = _rand_ensemble()
    with pytest.raises(ValueError, match="match"):
        quantize.quantize_pool_chunked(
            iter([np.zeros((4, ens.n_features + 1), np.float32)]),
            ens.borders)
    empty = quantize.quantize_pool_chunked(iter([]), ens.borders)
    assert empty.n_rows == 0 and empty.n_features == ens.n_features


def test_compute_borders_chunked_exact_when_under_sample_cap():
    x = _rand_x(_rand_ensemble(), 150, seed=5)
    want_b, want_c = quantize.compute_borders(x, max_bins=16)
    got_b, got_c = quantize.compute_borders_chunked(
        (x[s:s + 40] for s in range(0, len(x), 40)), max_bins=16,
        sample_rows=1024)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))


def test_compute_borders_chunked_sampled_shapes_and_validity():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(500, 3)).astype(np.float32)
    borders, counts = quantize.compute_borders_chunked(
        (x[s:s + 100] for s in range(0, 500, 100)), max_bins=8,
        sample_rows=128)
    assert borders.shape == (7, 3)
    assert np.all(np.asarray(counts) > 0)          # continuous columns
    b = np.asarray(borders)
    for j in range(3):                 # sample quantiles stay in range
        c = int(np.asarray(counts)[j])
        assert np.all(b[:c, j] < x[:, j].max())
    with pytest.raises(ValueError, match="non-empty"):
        quantize.compute_borders_chunked(iter([]))


# --------------------------------------------------------------------------
# Sources
# --------------------------------------------------------------------------
def test_array_source_and_iter_chunks():
    x = np.arange(20, dtype=np.float32).reshape(10, 2)
    src = ArraySource(x)
    assert (src.n_rows, src.n_features) == (10, 2)
    np.testing.assert_array_equal(src.read(3, 7), x[3:7])
    chunks = list(iter_chunks(src, 4))
    assert [c.shape[0] for c in chunks] == [4, 4, 2]
    np.testing.assert_array_equal(np.concatenate(chunks), x)
    with pytest.raises(ValueError, match="span"):
        src.read(5, 11)


def test_npy_memmap_source_roundtrip(tmp_path):
    x = np.random.default_rng(0).normal(size=(23, 5)).astype(np.float32)
    path = tmp_path / "x.npy"
    np.save(path, x)
    src = NpyMemmapSource(path)
    assert (src.n_rows, src.n_features) == (23, 5)
    np.testing.assert_array_equal(src.read(4, 9), x[4:9])


def test_synthetic_source_virtual_repeat():
    src = SyntheticSource("covertype", scale=0.001, split="test",
                          repeat=3)
    base = src.base_rows
    assert src.n_rows == 3 * base
    # rows wrap: the second tile equals the first
    np.testing.assert_array_equal(src.read(base, base + 5),
                                  src.read(0, 5))
    # a span crossing the tile boundary stitches correctly
    span = src.read(base - 2, base + 2)
    np.testing.assert_array_equal(span[:2], src.read(base - 2, base))
    np.testing.assert_array_equal(span[2:], src.read(0, 2))


# --------------------------------------------------------------------------
# Sinks
# --------------------------------------------------------------------------
def test_npy_sink_write_and_resume(tmp_path):
    path = tmp_path / "scores.npy"
    sink = NpySink(path)
    sink.open(6, 2)
    sink.write(0, np.ones((3, 2), np.float32))
    assert sink.close() == path
    # resume: rows written before survive, new rows land in place
    sink2 = NpySink(path, resume=True)
    sink2.open(6, 2)
    sink2.write(3, 2 * np.ones((3, 2), np.float32))
    sink2.close()
    out = np.load(path)
    np.testing.assert_array_equal(out[:3], 1.0)
    np.testing.assert_array_equal(out[3:], 2.0)
    # shape mismatch on resume is an error, not silent corruption
    sink3 = NpySink(path, resume=True)
    with pytest.raises(ValueError, match="resume"):
        sink3.open(7, 2)


def test_stats_sink_matches_numpy():
    rng = np.random.default_rng(1)
    ys = rng.normal(size=(90, 3)).astype(np.float32) * [1, 10, 0.1]
    sink = StatsSink()
    sink.open(90, 3)
    for s in range(0, 90, 13):
        sink.write(s, ys[s:s + 13])
    out = sink.close()
    assert out["count"] == 90
    np.testing.assert_allclose(out["mean"], ys.mean(0), rtol=1e-5)
    np.testing.assert_allclose(out["std"], ys.std(0), rtol=1e-5)
    np.testing.assert_allclose(out["min"], ys.min(0))
    np.testing.assert_allclose(out["max"], ys.max(0))


def test_topk_sink_matches_argsort():
    rng = np.random.default_rng(2)
    ys = rng.normal(size=(70, 2)).astype(np.float32)
    sink = TopKSink(5, column=1)
    sink.open(70, 2)
    for s in range(0, 70, 9):
        sink.write(s, ys[s:s + 9])
    out = sink.close()
    want = np.argsort(-ys[:, 1])[:5]
    np.testing.assert_array_equal(out["indices"], want)
    np.testing.assert_allclose(out["scores"], ys[want])
    # bottom-k flips the order
    lo = TopKSink(3, column=1, largest=False)
    lo.open(70, 2)
    lo.write(0, ys)
    np.testing.assert_array_equal(lo.close()["indices"],
                                  np.argsort(ys[:, 1])[:3])


def test_sink_write_validation():
    sink = ArraySink()
    with pytest.raises(ValueError, match="before"):
        sink.write(0, np.zeros((1, 2), np.float32))
    sink.open(4, 2)
    with pytest.raises(ValueError, match="width"):
        sink.write(0, np.zeros((1, 3), np.float32))
    with pytest.raises(ValueError, match="span"):
        sink.write(3, np.zeros((2, 2), np.float32))


# --------------------------------------------------------------------------
# Chunk planning
# --------------------------------------------------------------------------
def test_plan_chunks_two_shape_contract():
    spans = plan_chunks(n_rows=10_000, chunk_rows=1024)
    assert [s.start for s in spans[:2]] == [0, 1024]
    shapes = {s.padded for s in spans}
    assert len(shapes) <= 2
    tail = spans[-1]
    assert tail.n_valid == 10_000 - 9 * 1024
    assert tail.padded >= tail.n_valid          # bucket holds the tail
    assert tail.padded <= 1024


def test_best_chunk_rows_model_aware():
    small = tuning.best_chunk_rows(54, 7, n_borders=63, n_trees=100,
                                   n_leaves=32)
    nodims = tuning.best_chunk_rows(54, 7)
    assert small < nodims            # kernel working set shrinks chunks
    assert small & (small - 1) == 0            # power of two
    # a tiny dataset caps the chunk at its pow2 cover
    assert tuning.best_chunk_rows(54, 7, n_rows=300) <= 512


# --------------------------------------------------------------------------
# BulkScorer: parity + compile contract
# --------------------------------------------------------------------------
def test_bulk_scorer_matches_one_shot_exactly():
    ens = _rand_ensemble()
    plan = _plan(ens)
    x = _rand_x(ens, 150)
    res = BulkScorer(plan, ScoreConfig(chunk_rows=64, output="raw")) \
        .score(ArraySource(x))
    want = np.asarray(plan.raw(jnp.asarray(x)))
    np.testing.assert_array_equal(res.output, want)
    assert len(res.chunk_shapes) <= 2
    # the pool entry traced at most once per distinct chunk shape
    assert plan.stats["traces"].get("raw_pool", 0) <= len(res.chunk_shapes)


def test_bulk_scorer_proba_and_classify_shapes():
    ens = _rand_ensemble(n_outputs=3)
    plan = _plan(ens)
    x = _rand_x(ens, 50)
    proba = BulkScorer(plan, ScoreConfig(chunk_rows=32, output="proba")) \
        .score(ArraySource(x)).output
    np.testing.assert_array_equal(
        proba, np.asarray(plan.proba(jnp.asarray(x))))
    cls = BulkScorer(plan, ScoreConfig(chunk_rows=32,
                                       output="classify")) \
        .score(ArraySource(x)).output
    assert cls.shape == (50, 1)
    np.testing.assert_array_equal(
        cls[:, 0], np.asarray(plan.classify(jnp.asarray(x))))


def test_bulk_scorer_float_fallback_parity():
    """prequantize=False scores float chunks — same scores exactly."""
    ens = _rand_ensemble()
    plan = _plan(ens)
    x = _rand_x(ens, 90)
    res = BulkScorer(plan, ScoreConfig(chunk_rows=32, output="raw",
                                       prequantize=False)) \
        .score(ArraySource(x))
    np.testing.assert_array_equal(
        res.output, np.asarray(plan.raw(jnp.asarray(x))))


def test_bulk_scorer_single_binarize_trace_on_pool_path():
    """The prequantized pipeline binarizes only through the worker's
    quantize entry: across a whole run the registry sees exactly one
    binarize dispatch (the quantize entry's single trace — dispatch
    runs at trace time), never one per scoring entry."""
    ens = _rand_ensemble()
    plan = _plan(ens)
    x = _rand_x(ens, 150)
    registry.reset_call_stats()
    res = BulkScorer(plan, ScoreConfig(chunk_rows=64, output="raw")) \
        .score(ArraySource(x))
    stats = registry.call_stats()
    # every chunk (tail included) binarizes via the one full-chunk
    # quantize trace; the scoring entries trace without binarize
    assert stats.get("binarize", 0) == 1, stats
    assert stats.get("leaf_index", 0) >= 1, stats
    assert plan.stats["traces"].get("quantize", 0) == 1
    assert plan.stats["traces"].get("raw", 0) == 0   # float path unused
    assert len(res.chunk_shapes) == 2


# --------------------------------------------------------------------------
# Degenerate inputs (satellite): 0 rows, sub-chunk source, 1-row tail
# --------------------------------------------------------------------------
def test_zero_row_source():
    ens = _rand_ensemble(n_outputs=2)
    plan = _plan(ens)
    scorer = BulkScorer(plan, ScoreConfig(chunk_rows=32, output="raw"))
    res = scorer.score(ArraySource(np.zeros((0, ens.n_features),
                                            np.float32)))
    assert res.output.shape == (0, 2)
    assert res.metrics["chunks"] == 0
    assert res.metrics["compiles"] == 0          # no trace for no data
    assert res.chunk_shapes == ()


def test_source_smaller_than_one_chunk():
    ens = _rand_ensemble()
    plan = _plan(ens)
    x = _rand_x(ens, 5)
    res = BulkScorer(plan, ScoreConfig(chunk_rows=256, output="raw")) \
        .score(ArraySource(x))
    np.testing.assert_array_equal(
        res.output, np.asarray(plan.raw(jnp.asarray(x))))
    assert len(res.chunk_shapes) == 1


def test_one_row_tail_chunk():
    ens = _rand_ensemble()
    plan = _plan(ens)
    x = _rand_x(ens, 33)                         # 32 + a 1-row tail
    res = BulkScorer(plan, ScoreConfig(chunk_rows=32, output="raw")) \
        .score(ArraySource(x))
    np.testing.assert_array_equal(
        res.output, np.asarray(plan.raw(jnp.asarray(x))))
    assert len(res.chunk_shapes) <= 2
    assert plan.stats["traces"].get("raw_pool", 0) <= 2


def test_predict_pool_on_zero_row_pool():
    from repro.serving.engine import GBDTServer

    ens = _rand_ensemble(n_outputs=2)
    server = GBDTServer(ens, config=PredictConfig(strategy="staged",
                                                  backend="ref"),
                        max_batch=32)
    try:
        pool = QuantizedPool(
            jnp.zeros((0, ens.n_features), jnp.uint8),
            server.schema_fingerprint)
        out = server.predict_pool(pool)
        assert out.shape == (0, 2)
    finally:
        server.close()


# --------------------------------------------------------------------------
# Multi-model fan-out + schema sharing
# --------------------------------------------------------------------------
def test_multi_model_quantizes_once_per_schema():
    ens = _rand_ensemble(n_trees=12)
    plans = {"full": _plan(ens),
             "head": _plan(ens.slice_trees(0, 6)),
             "tail": _plan(ens.slice_trees(6, 12))}
    x = _rand_x(ens, 64)
    scorer = BulkScorer(plans, ScoreConfig(chunk_rows=32, output="raw"))
    registry.reset_call_stats()
    res = scorer.score(ArraySource(x))
    # 3 plans, 1 shared schema -> ONE binarize trace for the whole run
    assert registry.call_stats().get("binarize", 0) == 1
    # only the group's representative plan owns a quantize entry trace
    q_traces = {n: p.stats["traces"].get("quantize", 0)
                for n, p in plans.items()}
    assert sum(q_traces.values()) == 1, q_traces
    # fan-out sums: head + tail == full (same addends, regrouped)
    np.testing.assert_allclose(
        res.outputs["head"] + res.outputs["tail"],
        res.outputs["full"], rtol=1e-5, atol=1e-5)


def test_multi_model_feature_mismatch_rejected():
    a = _rand_ensemble(n_features=11)
    b = _rand_ensemble(seed=7, n_features=9)
    with pytest.raises(ValueError, match="feature count"):
        BulkScorer({"a": _plan(a), "b": _plan(b)})


# --------------------------------------------------------------------------
# Resume
# --------------------------------------------------------------------------
def test_resume_by_chunk_index(tmp_path):
    ens = _rand_ensemble()
    plan = _plan(ens)
    x = _rand_x(ens, 100)
    path = tmp_path / "scores.npy"
    cfg = ScoreConfig(chunk_rows=32, output="raw")
    BulkScorer(plan, cfg).score(ArraySource(x), NpySink(path))
    want = np.load(path).copy()

    # simulate an interrupted run: chunks 0-1 (rows [0, 64)) landed,
    # the process died; resume at chunk 2 into the surviving file
    partial = tmp_path / "resumed.npy"
    mm = np.lib.format.open_memmap(partial, mode="w+",
                                   dtype=np.float32, shape=want.shape)
    mm[:64] = want[:64]
    mm.flush()
    del mm
    res = BulkScorer(plan, cfg).score(
        ArraySource(x), NpySink(partial, resume=True), resume_from=2)
    assert res.metrics["resumed_from"] == 2
    assert res.metrics["rows"] == 100 - 64       # only remaining rows
    np.testing.assert_array_equal(np.load(partial), want)

    with pytest.raises(ValueError, match="resume_from"):
        BulkScorer(plan, cfg).score(ArraySource(x), resume_from=99)


# --------------------------------------------------------------------------
# score_source bridge + metrics units
# --------------------------------------------------------------------------
def test_server_score_source_matches_predict_batch():
    from repro.serving.engine import GBDTServer

    ens = _rand_ensemble(n_outputs=3)
    server = GBDTServer(ens, config=PredictConfig(strategy="staged",
                                                  backend="ref"),
                        max_batch=32)
    try:
        x = _rand_x(ens, 70)
        res = server.score_source(ArraySource(x), chunk_rows=32)
        np.testing.assert_allclose(res.output, server.predict_batch(x),
                                   rtol=1e-6, atol=1e-6)
        assert "rows_per_s" in res.metrics
        # online snapshot reports the same unit (shared dashboards)
        assert "rows_per_s" in server.metrics.snapshot()
        with pytest.raises(TypeError, match="not both"):
            server.score_source(ArraySource(x),
                                config=ScoreConfig(), chunk_rows=32)
    finally:
        server.close()


def test_percentile_reservoir_shared_and_bounded():
    r = PercentileReservoir(max_samples=64, seed=0)
    for v in range(1000):
        r.add(float(v))
    assert len(r) == 64 and r.seen == 1000
    assert 0.0 <= r.percentile(50) <= 999.0
    # both metrics classes sample through the same implementation
    assert isinstance(ServerMetrics("m")._lat, PercentileReservoir)
    assert isinstance(ScoringMetrics("b")._chunk_lat,
                      PercentileReservoir)


def test_scoring_metrics_snapshot_fields():
    m = ScoringMetrics("job")
    m.start()
    m.note_quantize(0.01)
    m.note_chunk(100, 128, 0.02)
    m.stop()
    snap = m.snapshot()
    assert snap["rows"] == 100 and snap["chunks"] == 1
    assert snap["rows_per_s"] > 0
    assert 0.0 < snap["quantize_frac"] < 1.0
    assert snap["pad_overhead"] == pytest.approx(28 / 128)


def test_scorer_rejects_bad_config_and_sinks():
    ens = _rand_ensemble()
    plan = _plan(ens)
    with pytest.raises(ValueError, match="output"):
        ScoreConfig(output="logits")
    with pytest.raises(ValueError, match="shard_axis"):
        ScoreConfig(shard_axis="columns")
    with pytest.raises(TypeError, match="not both"):
        BulkScorer(plan, ScoreConfig(), chunk_rows=64)
    with pytest.raises(ValueError, match="at least one"):
        BulkScorer({})
    scorer = BulkScorer({"a": plan, "b": plan})
    with pytest.raises(ValueError, match="no sink"):
        scorer.score(ArraySource(_rand_x(ens, 8)), {"a": ArraySink()})
    with pytest.raises(ValueError, match="single"):
        scorer.score(ArraySource(_rand_x(ens, 8)), ArraySink())


def test_bulk_scorer_through_streaming_sinks():
    """StatsSink/TopKSink reduce a scored stream without holding it."""
    ens = _rand_ensemble(n_outputs=2)
    plan = _plan(ens)
    x = _rand_x(ens, 80)
    res = BulkScorer(plan, ScoreConfig(chunk_rows=32, output="raw")) \
        .score(ArraySource(x), StatsSink())
    want = np.asarray(plan.raw(jnp.asarray(x)))
    assert res.output["count"] == 80
    np.testing.assert_allclose(res.output["mean"], want.mean(0),
                               rtol=1e-4, atol=1e-5)
    top = BulkScorer(plan, ScoreConfig(chunk_rows=32, output="raw")) \
        .score(ArraySource(x), TopKSink(4, column=0))
    np.testing.assert_array_equal(top.output["indices"],
                                  np.argsort(-want[:, 0])[:4])
