"""Serving path: bucket selection, bounded recompiles, fused parity on
unpadded ensembles, GBDTServer end-to-end, model registry."""
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import boosting, losses, predict
from repro.core.boosting import BoostingParams
from repro.core.predictor import PredictConfig
from repro.data import synthetic
from repro.kernels import ops, ref, tuning
from repro.serving import batching
from repro.serving.engine import GBDTServer, ModelRegistry


# --------------------------------------------------------------------------
# Bucket utilities
# --------------------------------------------------------------------------
def test_pow2_buckets_cover_max_batch():
    assert batching.pow2_buckets(256) == (16, 32, 64, 128, 256)
    assert batching.pow2_buckets(100) == (16, 32, 64, 128)
    assert batching.pow2_buckets(1, min_bucket=4) == (4,)
    assert batching.pow2_buckets(5, min_bucket=1) == (1, 2, 4, 8)


def test_bucket_for_picks_smallest_fit():
    buckets = (16, 64, 256)
    assert batching.bucket_for(1, buckets) == 16
    assert batching.bucket_for(16, buckets) == 16
    assert batching.bucket_for(17, buckets) == 64
    assert batching.bucket_for(256, buckets) == 256
    with pytest.raises(ValueError):
        batching.bucket_for(257, buckets)
    with pytest.raises(ValueError):
        batching.bucket_for(0, buckets)


def test_pad_rows():
    xs = np.arange(6, dtype=np.float32).reshape(3, 2)
    padded = batching.pad_rows(xs, 8)
    assert padded.shape == (8, 2)
    np.testing.assert_array_equal(padded[:3], xs)
    np.testing.assert_array_equal(padded[3:], 0.0)
    assert batching.pad_rows(xs, 3) is xs
    with pytest.raises(ValueError):
        batching.pad_rows(xs, 2)


def test_bucketed_batcher_pads_and_unpads():
    seen_shapes = []

    def serve(xs):
        seen_shapes.append(xs.shape[0])
        return xs.sum(axis=1)

    b = batching.BucketedBatcher(serve, max_batch=32, buckets=(8, 32))
    try:
        xs = np.ones((5, 3), np.float32)
        ys = b._run_batch(xs)
        assert ys.shape == (5,)                 # padding sliced off
        assert seen_shapes == [8]               # serve saw the bucket size
        assert b.bucket_counts[8] == 1
        b._run_batch(np.ones((9, 3), np.float32))
        assert seen_shapes == [8, 32]
    finally:
        b.close()


def test_batcher_counts_each_batchs_oldest_queue_wait():
    from repro.serving.metrics import ServerMetrics

    metrics = ServerMetrics("m")
    gate = threading.Event()

    def serve(xs):
        gate.wait(5)                # the first batch holds the batcher
        return xs.sum(axis=1)

    b = batching.BucketedBatcher(serve, max_batch=4, max_wait_ms=1.0,
                                 buckets=(4,), metrics=metrics)
    try:
        futs = [b.submit(i, np.ones(3, np.float32)) for i in range(8)]
        time.sleep(0.05)
        gate.set()
        for f in futs:
            f.get(timeout=5)
        snap = metrics.snapshot()
        assert snap["batches"] >= 2
        # a later batch's oldest request waited out the held first batch
        assert snap["queue_wait_max_s"] >= 0.05
        assert snap["queue_wait_s"] >= snap["queue_wait_max_s"]
        # a batch run directly never queued
        b._run_batch(np.ones((2, 3), np.float32))
        assert metrics.snapshot()["queue_wait_s"] == snap["queue_wait_s"]
        assert b.dispatch_queue_wait_s() == 0.0
    finally:
        b.close()


def test_queue_wait_merges_and_resets():
    from repro.serving.metrics import ServerMetrics

    a, b = ServerMetrics("m"), ServerMetrics("m")
    a.note_batch(2, 2, 0.001, queue_wait_s=0.004)
    a.note_batch(2, 2, 0.001, queue_wait_s=0.001)
    b.note_batch(2, 2, 0.001, queue_wait_s=0.003)
    merged = ServerMetrics.merge([a, b])
    assert merged["queue_wait_s"] == pytest.approx(0.008)
    assert merged["queue_wait_max_s"] == pytest.approx(0.004)
    a.reset()
    assert a.snapshot()["queue_wait_s"] == 0.0
    assert a.snapshot()["queue_wait_max_s"] == 0.0


def test_bucketed_batcher_rejects_undersized_buckets():
    with pytest.raises(ValueError):
        batching.BucketedBatcher(lambda x: x, max_batch=64, buckets=(8, 16))


# --------------------------------------------------------------------------
# Workload-aware block tuning
# --------------------------------------------------------------------------
def test_tuner_penalizes_padding_waste():
    # A 100-row workload must not be handed a 1024-row block.
    bn, bt = tuning.best_fused_blocks(54, 6, 64, 7, 255, n_rows=100,
                                      n_trees=40)
    assert bn <= 128
    assert bt <= 64
    # Without workload shape the original (unpenalized) choice stands.
    cands = tuning.candidates_fused(200, 8, 256, 7, 255)
    assert cands[0].score >= cands[-1].score


def test_ops_fused_autotunes_blocks():
    # No explicit blocks: ops picks them from the tuner; result must match
    # the reference on an oddly-shaped (unpadded) problem.
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(37, 11)).astype(np.float32))
    borders = jnp.asarray(np.sort(rng.normal(size=(9, 11)), 0)
                          .astype(np.float32))
    sf = jnp.asarray(rng.integers(0, 11, (13, 4)).astype(np.int32))
    sb = jnp.asarray(rng.integers(1, 9, (13, 4)).astype(np.int32))
    lv = jnp.asarray(rng.normal(size=(13, 16, 2)).astype(np.float32))
    got = ops.fused_predict(x, borders, sf, sb, lv, backend="pallas")
    want = ref.fused_predict(x, borders, sf, sb, lv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# --------------------------------------------------------------------------
# Server end-to-end
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cov_model():
    ds = synthetic.load("covertype", scale=0.003)
    loss = losses.make_loss("multiclass", n_classes=7)
    ens, _ = boosting.fit(ds.x_train, ds.y_train, loss=loss,
                          params=BoostingParams(n_trees=25, depth=5,
                                                learning_rate=0.3))
    return ens, ds


def test_server_recompiles_bounded_by_buckets(cov_model):
    ens, ds = cov_model
    server = GBDTServer(ens, strategy="fused", backend="ref",
                        max_batch=64, buckets=(16, 64))
    try:
        for n in (3, 5, 9, 16, 17, 33, 50, 64, 2, 40):
            out = server.predict_batch(ds.x_test[:n])
            assert out.shape == (n, 7)
        snap = server.metrics.snapshot()
        assert snap["recompiles"] <= len(server.buckets), snap
        assert snap["batches"] == 10
        assert snap["requests"] == 3 + 5 + 9 + 16 + 17 + 33 + 50 + 64 + 2 + 40
    finally:
        server.close()


def test_serve_batch_span_carries_queue_wait(cov_model):
    from repro.obs.trace import get_tracer, tracing

    ens, ds = cov_model
    server = GBDTServer(ens, strategy="staged", backend="ref",
                        max_batch=16, buckets=(16,))
    try:
        server.predict(ds.x_test[0])          # compile outside the ring
        tracer = get_tracer()
        with tracing(tracer, clear=True):
            server.predict(ds.x_test[1])
            server.predict_batch(ds.x_test[:3])
            events = [e for e in tracer.events()
                      if e["name"] == "serve/batch"]
        queued, direct = events
        assert queued["args"]["queue_wait_s"] > 0.0
        assert direct["args"]["queue_wait_s"] == 0.0   # never queued
        snap = server.metrics.snapshot()
        assert snap["queue_wait_s"] >= queued["args"]["queue_wait_s"]
    finally:
        server.close()


def test_server_fused_matches_staged_on_unpadded_ensemble(cov_model):
    # 25 trees of depth 5 / 54 features: nothing divides the kernel's
    # block multiples — the padding layer must make fused == staged.
    ens, ds = cov_model
    fused = GBDTServer(ens, strategy="fused", backend="ref", max_batch=64)
    staged = GBDTServer(ens, strategy="staged", backend="ref", max_batch=64)
    try:
        xs = ds.x_test[:100]
        np.testing.assert_allclose(fused.predict_batch(xs),
                                   staged.predict_batch(xs),
                                   rtol=1e-5, atol=1e-5)
    finally:
        fused.close()
        staged.close()


def test_server_fused_interpret_end_to_end():
    # Tiny model so Pallas interpret mode stays fast: full online path
    # (batcher thread -> bucket pad -> fused Pallas kernel -> unpad).
    rng = np.random.default_rng(0)
    x = rng.normal(size=(300, 6)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
    loss = losses.make_loss("logloss")
    ens, _ = boosting.fit(x, y, loss=loss,
                          params=BoostingParams(n_trees=8, depth=2,
                                                learning_rate=0.3))
    server = GBDTServer(ens, strategy="fused", backend="pallas",
                        max_batch=8, buckets=(8,), max_wait_ms=5.0)
    try:
        proba = server.predict(x[0])
        assert proba.shape == (2,)
        assert np.isfinite(proba).all()
        want = np.asarray(predict.predict_proba(
            ens, jnp.asarray(x[:1]), strategy="staged", backend="ref"))[0]
        np.testing.assert_allclose(proba, want, rtol=1e-5, atol=1e-5)
    finally:
        server.close()


def test_server_online_batcher_parity(cov_model):
    ens, ds = cov_model
    server = GBDTServer(ens, strategy="staged", backend="ref",
                        max_batch=32, max_wait_ms=1.0)
    try:
        got = server.predict(ds.x_test[0])
        want = np.asarray(predict.predict_proba(
            ens, jnp.asarray(ds.x_test[:1]), strategy="staged",
            backend="ref"))[0]
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    finally:
        server.close()


def test_predict_batch_chunks_oversized_input(cov_model):
    ens, ds = cov_model
    server = GBDTServer(ens, strategy="staged", backend="ref",
                        max_batch=16, buckets=(16,))
    try:
        out = server.predict_batch(ds.x_test[:40])   # 3 chunks: 16/16/8
        assert out.shape == (40, 7)
        assert server.metrics.snapshot()["batches"] == 3
        want = np.asarray(predict.predict_proba(
            ens, jnp.asarray(ds.x_test[:40]), strategy="staged",
            backend="ref"))
        np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    finally:
        server.close()


def test_server_accepts_predict_config(cov_model):
    # The compiled-plan path: one PredictConfig in, a resolved plan out,
    # no kwarg threading.
    ens, ds = cov_model
    server = GBDTServer(ens, config=PredictConfig(strategy="fused",
                                                  backend="ref"),
                        max_batch=32)
    try:
        assert server.config.is_resolved
        assert server.config.strategy == "fused"
        out = server.predict_batch(ds.x_test[:20])
        want = np.asarray(predict.predict_proba(
            ens, jnp.asarray(ds.x_test[:20]), strategy="staged",
            backend="ref"))
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
        # server recompile metrics are fed by the plan's trace counter
        assert server.predictor.stats["total_traces"] == \
            server.metrics.snapshot()["recompiles"]
    finally:
        server.close()


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------
def test_registry_serves_multiple_models(cov_model):
    ens, ds = cov_model
    reg = ModelRegistry(backend="ref", max_batch=32)
    try:
        reg.register("staged", ens, strategy="staged")
        reg.register("fused", ens, strategy="fused")
        assert reg.names() == ["fused", "staged"]
        a = reg.predict_batch("staged", ds.x_test[:20])
        b = reg.predict_batch("fused", ds.x_test[:20])
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        m = reg.metrics()
        assert m["staged"]["requests"] == 20
        assert m["fused"]["requests"] == 20
        with pytest.raises(KeyError):
            reg.register("fused", ens)
        with pytest.raises(KeyError):
            reg.get("nope")
        reg.unregister("staged")
        assert reg.names() == ["fused"]
    finally:
        reg.close()


def test_registry_swap_builds_fresh_plan(cov_model):
    # Predictor plans are immutable: swapping the ensemble under a name
    # must discard the old server and its plan caches wholesale.
    ens, ds = cov_model
    reg = ModelRegistry(backend="ref", max_batch=32)
    try:
        old = reg.register("m", ens)
        old_plan = old.predictor
        reg.predict_batch("m", ds.x_test[:4])
        new = reg.register("m", ens, replace=True)
        assert new is not old
        assert new.predictor is not old_plan
        assert new.predictor.stats["total_traces"] == 0
    finally:
        reg.close()


def test_compile_cache_placement(monkeypatch):
    """The launch CLIs' compile cache: JAX_COMPILATION_CACHE_DIR wins
    untouched; unset, a fixed `.jax_cache/` at the checkout root."""
    import jax
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
        assert compile_cache.configure() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv(compile_cache.ENV_VAR)
        placed = compile_cache.configure()
        assert placed == str(compile_cache.CHECKOUT / ".jax_cache")
        assert (compile_cache.CHECKOUT / "chip_smoke.py").is_file()
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
