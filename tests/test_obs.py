"""Observability subsystem: span tracer (disabled overhead, ring
eviction, Chrome-trace schema, cross-thread spans), the MetricsHub
exports, and the deadline-SLO accounting in ServerMetrics."""
import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictor import PredictConfig, Predictor
from repro.core.trees import ObliviousEnsemble
from repro.obs import MetricsHub
from repro.obs.trace import Tracer, get_tracer, tracing
from repro.scoring import ArraySink, ArraySource, BulkScorer, ScoreConfig
from repro.serving.metrics import ServerMetrics


def _rand_ensemble(seed=3, n_trees=9, depth=4, n_features=7,
                   n_borders=9, n_outputs=1):
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


# --------------------------------------------------------------------------
# Tracer core
# --------------------------------------------------------------------------
def test_disabled_span_is_shared_noop_and_records_nothing():
    # a disabled span is the bare profiler annotation (no longer a
    # shared singleton): it keeps nothing of its attributes and records
    # nothing in the ring
    tr = Tracer()
    s1 = tr.span("a", "cat", big_attr="x" * 100)
    s2 = tr.span("b")
    with s1:
        s1.set(result="dropped")
    with s2:
        pass
    tr.instant("i")
    tr.counter("c", v=1.0)
    tr.complete("x", start_ns=0, duration_ns=1)
    assert len(tr) == 0


def test_disabled_overhead_is_small():
    # the hot-path contract: a disabled span() call is an attribute
    # load + bool test.  Loose wall-clock bound (CI boxes are noisy) —
    # this catches accidental allocation/locking on the disabled path,
    # not nanosecond regressions.
    tr = Tracer()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("hot"):
            pass
    dt = time.perf_counter() - t0
    assert dt / n < 5e-6, f"{dt / n * 1e9:.0f}ns per disabled span"


def test_ring_eviction_is_fifo_and_counts_drops():
    tr = Tracer(capacity=4)
    tr.enable()
    for i in range(7):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert [e["name"] for e in tr.events()] == ["e3", "e4", "e5", "e6"]
    assert tr.dropped == 3


def test_span_records_duration_and_attrs():
    tr = Tracer()
    tr.enable()
    with tr.span("work", "cat", rows=128) as sp:
        sp.set(result="ok")
        time.sleep(0.002)
    (e,) = tr.events()
    assert e["ph"] == "X" and e["name"] == "work"
    assert e["dur_us"] >= 2000
    assert e["args"] == {"rows": 128, "result": "ok"}


def test_complete_event_matches_span_timebase():
    tr = Tracer()
    tr.enable()
    t0 = time.perf_counter_ns()
    tr.complete("pre-timed", "train", start_ns=t0, duration_ns=5000,
                level=2)
    with tr.span("live"):
        pass
    pre, live = tr.events()
    assert pre["dur_us"] == 5.0 and pre["args"] == {"level": 2}
    # same clock: the pre-timed event sits just before the live span
    assert pre["ts_us"] <= live["ts_us"]


def test_chrome_export_schema(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.span("bulk/score", "bulk", chunk=0):
        pass
    tr.instant("compile/raw", "compile", batch=64)
    tr.counter("queue_depth", "bulk", chunks=1.0)
    path = tmp_path / "trace.json"
    obj = tr.export_chrome(path)
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(obj))
    evs = loaded["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert metas and metas[0]["name"] == "thread_name"
    x = next(e for e in evs if e["ph"] == "X")
    assert {"name", "cat", "ts", "dur", "pid", "tid", "args"} <= set(x)
    assert isinstance(x["ts"], float) and x["pid"] == 1
    assert x["tid"] == 0                  # idents remapped to small ints
    i = next(e for e in evs if e["ph"] == "i")
    assert i["s"] == "t"
    c = next(e for e in evs if e["ph"] == "C")
    assert c["args"] == {"chunks": 1.0}
    assert loaded["otherData"]["dropped_events"] == 0


def test_export_names_threads_that_already_exited(tmp_path):
    tr = Tracer()
    tr.enable()

    def work():
        with tr.span("bg-span"):
            pass

    t = threading.Thread(target=work, name="my-worker")
    t.start()
    t.join()                    # the thread is dead before export
    obj = tr.export_chrome(tmp_path / "t.json")
    names = [e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M"]
    assert "my-worker" in names


def test_tracing_context_restores_prior_state():
    tr = Tracer()
    with tracing(tr):
        assert tr.enabled
        with tracing(tr):
            pass
        assert tr.enabled            # inner exit restores True
    assert not tr.enabled


# --------------------------------------------------------------------------
# Instrumentation integration: a traced BulkScorer run
# --------------------------------------------------------------------------
def _bulk_run(x, tracer, plan=None):
    """One traced BulkScorer run over x in 256-row chunks; its events."""
    plan = plan or Predictor.build(_rand_ensemble(),
                                   PredictConfig(strategy="staged",
                                                 backend="ref"))
    with tracing(tracer, clear=True):
        scorer = BulkScorer({"m": plan},
                            ScoreConfig(chunk_rows=256, prequantize=True))
        scorer.score(ArraySource(x), {"m": ArraySink()})
        return tracer.events()


def _by_name(events):
    out = {}
    for e in events:
        out.setdefault(e["name"], []).append(e)
    return out


def test_bulk_scorer_trace_shows_prefetch_overlap(tmp_path):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(700, 7)).astype(np.float32)
    tracer = get_tracer()
    by_name = _by_name(_bulk_run(x, tracer))
    # the pipeline spans all fired, once per chunk for quantize/score
    assert len(by_name["bulk/quantize"]) == len(by_name["bulk/score"])
    assert len(by_name["bulk/quantize"]) >= 3
    assert "bulk/sink" in by_name
    # the stage spans carry their chunk; the registry records no
    # dispatch span (it runs at trace time, not per chunk)
    for name in ("bulk/read", "bulk/quantize", "bulk/quantize_wait",
                 "bulk/score", "bulk/sync", "bulk/sink"):
        assert all({"chunk", "rows"} <= set(e["args"])
                   for e in by_name[name]), name
    assert not any(n.startswith("dispatch/") for n in by_name)
    # prefetch overlap: quantize happens on the worker thread, scoring
    # on the caller thread — distinct tids is what makes the overlap
    # visible on the exported timeline
    q_tids = {e["tid"] for e in by_name["bulk/quantize"]}
    s_tids = {e["tid"] for e in by_name["bulk/score"]}
    assert q_tids and s_tids and not (q_tids & s_tids)
    obj = tracer.export_chrome(tmp_path / "bulk.json")
    thread_labels = {e["args"]["name"] for e in obj["traceEvents"]
                     if e["ph"] == "M"}
    assert "prefetcher" in thread_labels
    assert not tracer.enabled        # context restored


_WORKER_SPANS = ("bulk/read", "bulk/quantize", "bulk/quantize_wait")
_MAIN_SPANS = ("bulk/prefetch_wait", "bulk/score", "bulk/sync", "bulk/sink")


def test_bulk_scorer_emits_each_stage_span_once_per_chunk_on_its_thread():
    x = np.random.default_rng(1).normal(size=(700, 7)).astype(np.float32)
    by_name = _by_name(_bulk_run(x, get_tracer()))
    n_chunks = 3                                 # 256 + 256 + 188-row tail
    for name in _WORKER_SPANS + _MAIN_SPANS:
        assert len(by_name[name]) == n_chunks, name
        if name != "bulk/prefetch_wait":        # waits before the chunk
            assert sorted(e["args"]["chunk"] for e in by_name[name]) \
                == list(range(n_chunks)), name
    main = threading.get_ident()
    assert {e["tid"] for n in _MAIN_SPANS for e in by_name[n]} == {main}
    worker = {e["tid"] for n in _WORKER_SPANS for e in by_name[n]}
    assert len(worker) == 1 and main not in worker


class _FencedBins:
    """Pool bins whose fence leaves a `fence` instant in the ring."""

    def __init__(self, bins, tracer):
        self._bins, self._tracer = bins, tracer
        self.shape, self.ndim, self.dtype = bins.shape, bins.ndim, bins.dtype

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._bins, dtype)

    def block_until_ready(self):
        self._tracer.instant("fence")
        self._bins.block_until_ready()
        return self


def test_bulk_quantize_span_holds_no_fence(monkeypatch):
    from repro.core.quantize import QuantizedPool

    plan = Predictor.build(_rand_ensemble(),
                           PredictConfig(strategy="staged", backend="ref"))
    tracer = get_tracer()
    quantize = plan.quantize

    def fenced(x):
        pool = quantize(x)
        return QuantizedPool(_FencedBins(pool.bins, tracer),
                             pool.fingerprint)

    monkeypatch.setattr(plan, "quantize", fenced)
    x = np.random.default_rng(2).normal(size=(768, 7)).astype(np.float32)
    by_name = _by_name(_bulk_run(x, tracer, plan))

    def inside(t, spans):
        return [e for e in spans
                if e["ts_us"] <= t <= e["ts_us"] + e["dur_us"]]

    fences = [e["ts_us"] for e in by_name["fence"]]
    assert len(fences) == 3
    for t in fences:
        assert len(inside(t, by_name["bulk/quantize_wait"])) == 1
        assert not inside(t, by_name["bulk/quantize"])


def test_bulk_spans_reach_the_profiler_with_the_ring_off(tmp_path):
    import pathlib
    import sys

    bench = pathlib.Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    from harness import trace as bench_trace

    plan = Predictor.build(_rand_ensemble(),
                           PredictConfig(strategy="staged", backend="ref"))
    scorer = BulkScorer(plan, ScoreConfig(chunk_rows=256))
    x = np.random.default_rng(3).normal(size=(700, 7)).astype(np.float32)
    scorer.score(ArraySource(x))                 # compile outside the trace
    tracer = get_tracer()
    tracer.clear()
    assert not tracer.enabled
    with bench_trace.capture(tmp_path) as found:
        scorer.score(ArraySource(x))
    assert len(tracer) == 0                      # the ring stayed empty
    host = bench_trace.load(found[0]).host
    names = [e.name for e in host]
    for name in ("bulk/sync", "bulk/prefetch_wait"):
        assert names.count(name) == 3, name
    # on the profiler's clock: the sweep's spans lie inside the window
    (window,) = [e for e in host if e.name == bench_trace.WINDOW_SPAN]
    for e in host:
        if e.name.startswith("bulk/"):
            assert window.start_ns <= e.start_ns <= e.end_ns <= window.end_ns


# --------------------------------------------------------------------------
# Deadline-SLO accounting
# --------------------------------------------------------------------------
def test_server_metrics_slo_math():
    m = ServerMetrics("m", deadline_ms=10.0)
    m.note_batch(4, 8, 0.005)        # 5ms: hit, 4 rows
    m.note_batch(2, 2, 0.020)        # 20ms: miss, 2 rows
    m.note_shed(3)
    s = m.snapshot()
    assert s["deadline_hits"] == 4 and s["deadline_misses"] == 2
    assert s["deadline_attainment"] == pytest.approx(4 / 6)
    assert s["shed_requests"] == 3
    assert s["shed_rate"] == pytest.approx(3 / 9)   # 6 served + 3 shed
    # p99-under-deadline sees only the 5ms batch; overall p99 sees both
    assert s["p99_under_deadline_ms"] == pytest.approx(5.0)
    assert s["batch_p99_ms"] > 5.0


def test_server_metrics_slo_disabled_is_vacuous():
    m = ServerMetrics("m")
    m.note_batch(4, 4, 0.5)
    s = m.snapshot()
    assert s["deadline_ms"] is None
    assert s["deadline_attainment"] == 1.0
    assert s["deadline_hits"] == 0 and s["deadline_misses"] == 0
    assert s["shed_rate"] == 0.0


def test_server_metrics_interval_rates_and_reset():
    m = ServerMetrics("m")
    m.note_batch(10, 10, 0.001)
    s1 = m.snapshot()
    assert s1["interval_requests_per_s"] > 0
    s2 = m.snapshot()                 # nothing since the last poll
    assert s2["interval_requests_per_s"] == 0.0
    assert s2["requests_per_s"] > 0   # lifetime rate persists
    m.reset()
    s3 = m.snapshot()
    assert s3["requests"] == 0 and s3["batch_p99_ms"] == 0.0


def test_server_metrics_merge_does_not_consume_intervals():
    a, b = ServerMetrics("m", deadline_ms=5.0), \
        ServerMetrics("m", deadline_ms=5.0)
    a.note_batch(3, 4, 0.001)
    b.note_batch(5, 8, 0.009)
    fleet = ServerMetrics.merge([a, b])
    assert fleet["replicas"] == 2 and fleet["requests"] == 8
    assert fleet["deadline_hits"] == 3 and fleet["deadline_misses"] == 5
    assert fleet["deadline_attainment"] == pytest.approx(3 / 8)
    # the merge read must not have eaten either part's interval window
    assert a.snapshot()["interval_requests_per_s"] > 0
    assert b.snapshot()["interval_requests_per_s"] > 0


# --------------------------------------------------------------------------
# MetricsHub
# --------------------------------------------------------------------------
def test_hub_register_forms_and_snapshot():
    hub = MetricsHub()
    m = ServerMetrics("m")
    hub.register("serving/m", m)                       # .snapshot()
    hub.register("adhoc", lambda: {"x": 1})            # callable
    hub.register("static", {"y": 2.5})                 # mapping
    with pytest.raises(KeyError):
        hub.register("adhoc", lambda: {})              # no silent shadow
    hub.register("adhoc", lambda: {"x": 9}, replace=True)
    snap = hub.snapshot()
    assert snap["adhoc"] == {"x": 9} and snap["static"] == {"y": 2.5}
    assert snap["serving/m"]["requests"] == 0
    assert hub.namespaces() == ["adhoc", "serving/m", "static"]


def test_hub_failing_source_is_isolated():
    hub = MetricsHub()

    def boom():
        raise RuntimeError("dead model")

    hub.register("bad", boom)
    hub.register("good", {"ok": 1})
    snap = hub.snapshot()
    assert snap["good"] == {"ok": 1}
    assert "RuntimeError" in snap["bad"]["error"]


def test_hub_prometheus_format(tmp_path):
    hub = MetricsHub(prefix="repro")
    hub.register("scoring/bulk", {"rows_per_s": 1234.5, "rows": 10,
                                  "model": "cover type", "exact": True,
                                  "nested": {"raw": 3},
                                  "skipme": [1, 2]})
    text = hub.export_prometheus(tmp_path / "m.prom")
    assert (tmp_path / "m.prom").read_text() == text
    assert "# TYPE repro_scoring_bulk_rows_per_s gauge" in text
    assert 'model="cover type"' in text
    assert "repro_scoring_bulk_rows_per_s" in text
    assert "repro_scoring_bulk_exact" in text          # bool -> gauge
    assert "repro_scoring_bulk_nested_raw" in text     # one-level flatten
    assert "skipme" not in text                        # lists skipped


def test_hub_json_export(tmp_path):
    hub = MetricsHub()
    hub.register("a", {"v": 1})
    obj = hub.export_json(tmp_path / "m.json")
    loaded = json.loads((tmp_path / "m.json").read_text())
    assert loaded["metrics"]["a"]["v"] == 1
    assert "collected_at" in loaded and "collected_at" in obj


# --------------------------------------------------------------------------
# Names on the device trace
# --------------------------------------------------------------------------
def test_every_pallas_call_passes_a_name():
    """A pallas_call without `name=` takes its op name from the
    enclosing jit, which a rename would silently change under the
    benchmark's trace readers."""
    import ast
    import pathlib

    kernels = pathlib.Path(__file__).resolve().parents[1] / "src" / \
        "repro" / "kernels"
    calls = []
    for path in sorted(kernels.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "pallas_call":
                calls.append((path.name, node.lineno,
                              {k.arg for k in node.keywords}))
    assert len(calls) == 8
    assert [c[:2] for c in calls if "name" not in c[2]] == []


def test_export_names_a_thread_by_its_latest_name(tmp_path):
    # the OS reuses a dead thread's ident for the next thread: the label
    # follows the latest thread to record under an ident, never the first
    tr = Tracer()
    tr.enable()

    def work():
        tr.instant("before")
        threading.current_thread().name = "renamed-worker"
        tr.instant("after")

    t = threading.Thread(target=work, name="first-worker")
    t.start()
    t.join(timeout=5)
    assert not t.is_alive()
    obj = tr.export_chrome(tmp_path / "t.json")
    labels = [e["args"]["name"] for e in obj["traceEvents"]
              if e["ph"] == "M"]
    assert labels == ["renamed-worker"]
