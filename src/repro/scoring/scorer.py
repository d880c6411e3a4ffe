"""Streaming out-of-core bulk scoring: apply compiled Predictor plans
to arbitrarily large datasets at device speed.

This is the offline half of the serving stack — the paper's headline
workload (`ApplyModelMulti` sweeping whole datasets through a prepared
ensemble) as a production "nightly rescore" subsystem.  The online path
(PRs 1-4) binds throughput to request traffic; `BulkScorer` binds it to
the hardware:

  * **one fixed chunk shape** — the planner picks a single power-of-two
    chunk (`kernels.tuning.best_chunk_rows`, host-memory-budgeted) and
    bucket-pads the tail chunk via `QuantizedPool.pad_rows`, so the
    whole run traces at most 2 XLA shapes no matter the dataset size;
  * **O(chunk) host memory** — rows are range-read from a `RowSource`
    and quantized per chunk (`quantize_pool` on the chunk, never the
    dataset), scores stream row-addressed into a `ScoreSink`; nothing
    dataset-sized is ever resident;
  * **pipelined quantization** — a `data.pipeline.Prefetcher` worker
    reads + binarizes chunk k+1 while the main thread's jax dispatch
    scores chunk k (device compute is async; the host sync point is the
    sink write);
  * **multi-model fan-out** — K plans score every chunk; plans sharing
    a quantization schema (`borders_fingerprint`) share one pool per
    chunk, the offline analogue of `ModelRegistry.predict_multi`;
  * **resume by chunk index** — chunk boundaries are a pure function of
    (n_rows, chunk_rows), so an interrupted run restarts at
    ``resume_from=k`` and row-addressed sinks (`NpySink(resume=True)`)
    keep the rows already scored.

    cfg    = ScoreConfig(output="proba")
    scorer = BulkScorer(plan, cfg)           # or {"name": plan, ...}
    result = scorer.score(NpyMemmapSource("x.npy"), NpySink("y.npy"))
    result.metrics["rows_per_s"]             # comparable to ServerMetrics
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Mapping, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.predictor import (Predictor, classify_from_raw,
                                  proba_from_raw)
from repro.core.quantize import MAX_BINS
from repro.data.pipeline import Prefetcher
from repro.kernels import tuning
from repro.obs.trace import get_tracer
from repro.scoring.sinks import ArraySink, ScoreSink
from repro.scoring.sources import RowSource
from repro.serving.batching import bucket_for, pad_rows, pow2_buckets
from repro.serving.metrics import PercentileReservoir

_OUTPUTS = ("raw", "proba", "classify")

_TRACER = get_tracer()


def _chunk_span(name: str, span: "ChunkSpan", **attrs: Any):
    """A `bulk/*` span of one chunk; its ring attributes are built only
    while the ring records."""
    if not _TRACER.enabled:
        return _TRACER.span(name)
    return _TRACER.span(name, "bulk", chunk=span.index, rows=span.n_valid,
                        **attrs)


@dataclasses.dataclass(frozen=True)
class ScoreConfig:
    """Bulk-scoring configuration.

      chunk_rows      fixed chunk shape; 0 = auto from
                      `tuning.best_chunk_rows` (host-budgeted pow2)
      output          which plan entry scores: raw | proba | classify
                      (classify lands in sinks as an (N, 1) panel)
      prefetch_depth  chunks in flight ahead of the scorer (the
                      Prefetcher queue bound); 0 = synchronous, no
                      worker thread
      prequantize     binarize each chunk on the prefetch worker and
                      score uint8 pools (binarize leaves the critical
                      path entirely); plans whose borders exceed the
                      uint8 cap fall back to the float path per schema
      chunk_budget_bytes   host bytes one in-flight chunk may cost
                      (feeds the auto chunk planner)
      shard_axis      how a mesh scorer splits the work: rows (exact
                      parity with one device) | trees | auto
                      (`tuning.best_shard_axis` per chunk)
    """
    chunk_rows: int = 0
    output: str = "proba"
    prefetch_depth: int = 2
    prequantize: bool = True
    chunk_budget_bytes: int = tuning.CHUNK_BUDGET_BYTES
    shard_axis: str = "auto"

    def __post_init__(self):
        if self.output not in _OUTPUTS:
            raise ValueError(f"output must be one of {_OUTPUTS}, "
                             f"got {self.output!r}")
        if not isinstance(self.chunk_rows, int) or self.chunk_rows < 0:
            raise ValueError(f"chunk_rows must be an int >= 0, "
                             f"got {self.chunk_rows!r}")
        if not isinstance(self.prefetch_depth, int) \
                or self.prefetch_depth < 0:
            raise ValueError(f"prefetch_depth must be an int >= 0, "
                             f"got {self.prefetch_depth!r}")
        if self.chunk_budget_bytes < 1:
            raise ValueError("chunk_budget_bytes must be positive")
        if self.shard_axis not in ("auto", "rows", "trees"):
            raise ValueError(f"shard_axis must be auto|rows|trees, "
                             f"got {self.shard_axis!r}")


class ScoringMetrics:
    """Offline counterpart of `serving.metrics.ServerMetrics`: rows/s,
    the quantize-vs-score wall split, chunk count, XLA compiles, and
    per-chunk latency percentiles through the same
    `PercentileReservoir` — so online and offline dashboards report
    comparable units (`rows_per_s` appears in both snapshots)."""

    def __init__(self, name: str = "bulk"):
        self.name = name
        self._lock = threading.Lock()
        self.rows = 0
        self.padded_rows = 0
        self.chunks = 0
        self.quantize_s = 0.0
        self.score_s = 0.0
        self.wall_s = 0.0
        self.compiles = 0
        self.resumed_from = 0
        self._chunk_lat = PercentileReservoir()
        self._t0: Optional[float] = None
        # interval-rate markers: state of the previous snapshot() call
        self._prev_t = time.perf_counter()
        self._prev_rows = 0

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        if self._t0 is not None:
            self.wall_s += time.perf_counter() - self._t0
            self._t0 = None

    def reset(self) -> None:
        """Zero all counters and restart the rate clocks (the name
        survives).  A running interval (`start()` without `stop()`)
        restarts from now."""
        with self._lock:
            self.rows = self.padded_rows = self.chunks = 0
            self.quantize_s = self.score_s = self.wall_s = 0.0
            self.compiles = self.resumed_from = 0
            self._chunk_lat = PercentileReservoir()
            now = time.perf_counter()
            if self._t0 is not None:
                self._t0 = now
            self._prev_t = now
            self._prev_rows = 0

    def note_quantize(self, seconds: float) -> None:
        """Called from the prefetch worker thread."""
        with self._lock:
            self.quantize_s += seconds

    def note_chunk(self, n_valid: int, n_padded: int,
                   score_seconds: float) -> None:
        with self._lock:
            self.chunks += 1
            self.rows += n_valid
            self.padded_rows += n_padded - n_valid
            self.score_s += score_seconds
            self._chunk_lat.add(score_seconds)

    def _locked_snapshot(self, advance_interval: bool) -> dict[str, Any]:
        """Build the snapshot dict; caller holds self._lock.

        `wall_s` includes the in-progress interval when called between
        `start()` and `stop()`, so a mid-run snapshot's `rows_per_s` is
        live, not the value frozen at the last `stop()`."""
        now = time.perf_counter()
        wall = self.wall_s + (now - self._t0
                              if self._t0 is not None else 0.0)
        idt = max(now - self._prev_t, 1e-9)
        busy = self.quantize_s + self.score_s
        pad_total = self.rows + self.padded_rows
        snap = {
            "name": self.name,
            "rows": self.rows,
            "chunks": self.chunks,
            "compiles": self.compiles,
            "resumed_from": self.resumed_from,
            "wall_s": wall,
            "rows_per_s": self.rows / wall if wall else 0.0,
            "interval_rows_per_s": (self.rows - self._prev_rows) / idt,
            "quantize_s": self.quantize_s,
            "score_s": self.score_s,
            # note quantize overlaps score on the worker thread, so
            # the fractions describe where the work went, not wall
            "quantize_frac": self.quantize_s / busy if busy else 0.0,
            "chunk_p50_ms": self._chunk_lat.percentile(50) * 1e3,
            "chunk_p99_ms": self._chunk_lat.percentile(99) * 1e3,
            "pad_overhead": (self.padded_rows / pad_total
                             if pad_total else 0.0),
        }
        if advance_interval:
            self._prev_t = now
            self._prev_rows = self.rows
        return snap

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return self._locked_snapshot(advance_interval=True)

    @staticmethod
    def merge(parts: list["ScoringMetrics"]) -> dict[str, Any]:
        """One fleet view over per-shard/per-worker bulk metrics.

        Mirrors `ServerMetrics.merge`: counts, compile totals and the
        throughput rates sum (K workers at X rows/s really move K*X
        fleet rows/s), wall is the slowest part (shards run
        concurrently), and chunk-latency percentiles come from the
        merged reservoirs, not averaged per-shard percentiles."""
        if not parts:
            raise ValueError("ScoringMetrics.merge needs at least one "
                             "part")
        # one locked pass per part: snapshot fields and the reservoir
        # come from the same instant (and the non-advancing read leaves
        # each part's interval window to its own poller)
        snaps = []
        lat = PercentileReservoir()
        pad_rows = rows = 0
        for p in parts:
            with p._lock:
                snaps.append(p._locked_snapshot(advance_interval=False))
                lat.merge(p._chunk_lat)
                pad_rows += p.padded_rows
                rows += p.rows
        busy = (sum(s["quantize_s"] for s in snaps)
                + sum(s["score_s"] for s in snaps))
        pad_total = rows + pad_rows
        return {
            "name": snaps[0]["name"],
            "parts": len(parts),
            "rows": rows,
            "chunks": sum(s["chunks"] for s in snaps),
            "compiles": sum(s["compiles"] for s in snaps),
            "resumed_from": min(s["resumed_from"] for s in snaps),
            "wall_s": max(s["wall_s"] for s in snaps),
            "rows_per_s": sum(s["rows_per_s"] for s in snaps),
            "interval_rows_per_s": sum(s["interval_rows_per_s"]
                                       for s in snaps),
            "quantize_s": sum(s["quantize_s"] for s in snaps),
            "score_s": sum(s["score_s"] for s in snaps),
            "quantize_frac": (sum(s["quantize_s"] for s in snaps) / busy
                              if busy else 0.0),
            "chunk_p50_ms": lat.percentile(50) * 1e3,
            "chunk_p99_ms": lat.percentile(99) * 1e3,
            "pad_overhead": (pad_rows / pad_total if pad_total else 0.0),
        }

    def __repr__(self) -> str:
        s = self.snapshot()
        return (f"<ScoringMetrics {s['name']}: {s['rows']} rows in "
                f"{s['chunks']} chunks, {s['rows_per_s']:.0f} rows/s, "
                f"quantize {s['quantize_frac']:.0%} of busy time>")


@dataclasses.dataclass(frozen=True)
class ChunkSpan:
    """One planned chunk: rows [start, stop) padded up to `padded`."""
    index: int
    start: int
    stop: int
    padded: int

    @property
    def n_valid(self) -> int:
        return self.stop - self.start


def plan_chunks(n_rows: int, chunk_rows: int) -> tuple[ChunkSpan, ...]:
    """Cut n_rows into fixed `chunk_rows` spans; the tail span is
    padded to the smallest power-of-two bucket holding it (so a run is
    at most 2 distinct padded shapes: the chunk and one tail bucket)."""
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    ladder = pow2_buckets(chunk_rows, min_bucket=min(16, chunk_rows))
    spans = []
    for i, start in enumerate(range(0, n_rows, chunk_rows)):
        stop = min(start + chunk_rows, n_rows)
        n = stop - start
        padded = chunk_rows if n == chunk_rows else bucket_for(n, ladder)
        spans.append(ChunkSpan(i, start, stop, padded))
    return tuple(spans)


@dataclasses.dataclass(frozen=True)
class ScoreResult:
    """What a bulk run produced: per-model sink results, the metrics
    snapshot, and the compile-contract evidence (`chunk_shapes` is the
    set of padded shapes the jitted entries saw — always <= 2)."""
    outputs: dict[str, Any]
    metrics: dict[str, Any]
    chunk_rows: int
    chunk_shapes: tuple[int, ...]
    n_rows: int

    @property
    def output(self) -> Any:
        """Single-model convenience accessor."""
        if len(self.outputs) != 1:
            raise ValueError(f"run scored {sorted(self.outputs)}; pick "
                             "one from .outputs")
        return next(iter(self.outputs.values()))


@dataclasses.dataclass
class _SchemaGroup:
    """Plans sharing one quantization schema: quantize once per chunk.

    `rep` is the group's representative plan — its jitted `quantize`
    entry binarizes every full chunk (one XLA shape for the whole run;
    pools are schema-wide shareable, so any group member works).  The
    tail chunk goes through the eager `quantize_pool` + `pad_rows`
    instead, keeping the jitted quantize cache at exactly one shape.
    """
    fingerprint: str
    borders: Any
    backend: str
    use_pool: bool
    rep: Predictor
    names: list[str]


class BulkScorer:
    """Apply one or many compiled `Predictor` plans to a `RowSource`,
    streaming scores into `ScoreSink`s (see module docstring).

    Pass a single plan or a ``{name: Predictor}`` mapping; all plans
    must agree on feature count (they read the same source).  The
    scorer is stateless across runs — `score` may be called repeatedly
    (the plans' jit caches persist, so later runs skip compilation).
    """

    def __init__(self, plans: Predictor | Mapping[str, Predictor],
                 config: Optional[ScoreConfig] = None, *,
                 mesh=None, **config_kw: Any):
        if config is None:
            config = ScoreConfig(**config_kw)
        elif config_kw:
            raise TypeError("pass either a ScoreConfig or config kwargs, "
                            f"not both: {sorted(config_kw)}")
        self.config = config
        # mesh mode: every chunk's rows shard across the mesh through
        # the plan's `sharded()` pool/float entries (full registry
        # dispatch per shard, exact row-shard parity).  The streaming
        # contracts hold unchanged: the chunk planner still fixes <= 2
        # padded shapes, host memory stays O(chunk), and the Prefetcher
        # still binarizes chunk k+1 while chunk k's shards score —
        # prequantized chunks quantize once on the worker and shard
        # their uint8 bins; the float fallback binarizes shard-locally
        # inside the mesh body.
        self.mesh = mesh
        if isinstance(plans, Predictor):
            plans = {"model": plans}
        self.plans = dict(plans)
        if not self.plans:
            raise ValueError("BulkScorer needs at least one plan")
        for name, plan in self.plans.items():
            if not isinstance(plan, Predictor):
                raise TypeError(f"plans[{name!r}] is {type(plan).__name__},"
                                " not a Predictor (build one with "
                                "Predictor.build)")
        feats = {p.ensemble.n_features for p in self.plans.values()}
        if len(feats) > 1:
            raise ValueError(f"plans disagree on feature count {feats}; "
                             "one source feeds them all")
        self.n_features = feats.pop()
        # quantize once per schema fingerprint, score every plan in the
        # group from that pool (the predict_multi pattern, offline)
        self._groups: dict[str, _SchemaGroup] = {}
        for name, plan in self.plans.items():
            fp = plan.schema_fingerprint
            g = self._groups.get(fp)
            if g is None:
                can_pool = (config.prequantize and
                            plan.ensemble.borders.shape[0] <= MAX_BINS - 1)
                g = _SchemaGroup(fp, plan.ensemble.borders,
                                 plan.config.backend, can_pool, plan, [])
                self._groups[fp] = g
            g.names.append(name)
        self._group_of = {name: g for g in self._groups.values()
                          for name in g.names}

    # -- planning ----------------------------------------------------------
    def resolve_chunk_rows(self, n_rows: int) -> int:
        if self.config.chunk_rows:
            return self.config.chunk_rows
        ensembles = [p.ensemble for p in self.plans.values()]
        return tuning.best_chunk_rows(
            self.n_features,
            max(e.n_outputs for e in ensembles),
            n_borders=max(int(e.borders.shape[0]) for e in ensembles),
            n_trees=max(e.n_trees for e in ensembles),
            n_leaves=max(int(e.leaf_values.shape[1]) for e in ensembles),
            budget_bytes=self.config.chunk_budget_bytes, n_rows=n_rows)

    def _output_width(self, plan: Predictor) -> int:
        c = plan.ensemble.n_outputs
        if self.config.output == "raw":
            return c
        if self.config.output == "proba":
            return max(c, 2)
        return 1                                    # classify

    # -- the run -----------------------------------------------------------
    def _prepare(self, metrics: ScoringMetrics, chunk_rows: int):
        """Build the prefetch transform: pad the chunk to its planned
        shape and binarize it once per schema group.  Runs on the
        Prefetcher worker thread — chunk k+1 quantizes while the main
        thread's dispatch scores chunk k."""
        def prepare(item):
            span, x = item
            t0 = time.perf_counter()
            payload: dict[str, Any] = {}
            # these spans land on the Prefetcher worker's thread, so the
            # timeline shows chunk k+1's quantize riding under chunk k's
            # bulk/score on the main thread; the dispatch and the fence
            # are apart, since the fence waits behind chunk k's trees
            with _chunk_span("bulk/quantize", span, padded=span.padded):
                need_float = any(not g.use_pool
                                 for g in self._groups.values())
                if need_float:
                    payload["__float__"] = jnp.asarray(
                        pad_rows(x, span.padded), jnp.float32)
                for fp, g in self._groups.items():
                    if g.use_pool:
                        # every chunk — the tail too — binarizes through
                        # the representative plan's jitted quantize entry
                        # at the one full-chunk shape (a zero-padded float
                        # row bins to 0, exactly what pool padding yields)
                        pool = g.rep.quantize(
                            x if span.n_valid == chunk_rows
                            else pad_rows(x, chunk_rows))
                        if span.padded != chunk_rows:
                            # tail: slice the valid rows back out and
                            # bucket-pad the pool to the planned tail shape
                            pool = pool.slice_rows(0, span.n_valid) \
                                       .pad_rows(span.padded)
                        payload[fp] = pool
            # force the binarize to finish HERE, on the worker thread:
            # jax dispatch is async, and an unfinished pool would push
            # the quantize work onto the main thread's sync point,
            # killing the overlap
            with _chunk_span("bulk/quantize_wait", span):
                for fp, g in self._groups.items():
                    if g.use_pool:
                        payload[fp].bins.block_until_ready()
            metrics.note_quantize(time.perf_counter() - t0)
            return span, payload
        return prepare

    def _score_entry(self, plan: Predictor, x) -> np.ndarray:
        out = self.config.output
        if self.mesh is not None:
            raw = plan.sharded(self.mesh,
                               shard_axis=self.config.shard_axis)(x)
            if out == "raw":
                return raw
            if out == "proba":
                return proba_from_raw(raw, plan.ensemble.n_outputs)
            return classify_from_raw(raw, plan.ensemble.n_outputs)
        if out == "raw":
            return plan.raw(x)
        if out == "proba":
            return plan.proba(x)
        return plan.classify(x)

    def score(self, source: RowSource, sinks=None, *,
              resume_from: int = 0) -> ScoreResult:
        """Stream the whole source through every plan.

        `sinks` is a ``{name: ScoreSink}`` mapping, a single sink (for
        single-plan scorers), or None (fresh `ArraySink` per plan —
        the whole output in host memory; pass `NpySink`s to stay
        out-of-core).  ``resume_from=k`` skips chunks < k: chunk
        boundaries depend only on (n_rows, chunk_rows), so a resumed
        run lands its rows at identical positions — pair with
        row-addressed sinks (`NpySink(resume=True)`); the streaming
        reducer sinks fold only the remaining rows.
        """
        if source.n_features != self.n_features:
            raise ValueError(f"source has {source.n_features} features, "
                             f"plans expect {self.n_features}")
        n_rows = source.n_rows
        chunk_rows = self.resolve_chunk_rows(n_rows)
        spans = plan_chunks(n_rows, chunk_rows)
        if not 0 <= resume_from <= len(spans):
            raise ValueError(f"resume_from={resume_from} outside "
                             f"[0, {len(spans)}] for {len(spans)} chunks "
                             f"of {chunk_rows} rows")
        todo = spans[resume_from:]

        sinks = self._normalize_sinks(sinks)
        for name, plan in self.plans.items():
            sinks[name].open(n_rows, self._output_width(plan))

        metrics = ScoringMetrics()
        metrics.resumed_from = resume_from
        traces0 = sum(p.stats["total_traces"] for p in self.plans.values())
        metrics.start()

        def read_spans():
            for span in todo:
                with _chunk_span("bulk/read", span):
                    x = source.read(span.start, span.stop)
                yield span, x

        prepare = self._prepare(metrics, chunk_rows)
        if self.config.prefetch_depth > 0 and len(todo) > 1:
            stream = Prefetcher(read_spans(),
                                depth=self.config.prefetch_depth,
                                transform=prepare)
        else:
            stream = map(prepare, read_spans())

        def drain(entry):
            span, outs, t0 = entry
            with _chunk_span("bulk/sync", span):
                # host sync point: waits for the chunk's device work
                host = {name: np.asarray(ys, np.float32)
                        for name, ys in outs.items()}
            with _chunk_span("bulk/sink", span):
                for name, ys in host.items():
                    if ys.ndim == 1:                  # classify: (N,) ids
                        ys = ys[:, None]
                    sinks[name].write(span.start, ys[:span.n_valid])
            metrics.note_chunk(span.n_valid, span.padded,
                               time.perf_counter() - t0)

        # lag-1 sync: dispatch chunk k+1's entries before forcing chunk
        # k's device->host copy, so jax's async dispatch keeps the
        # device busy while python writes sinks (pending is bounded at
        # 2 chunks — the O(chunk) memory contract includes it)
        pending: list = []
        chunks = iter(stream)
        try:
            for _ in todo:
                # the main thread idles here when the worker's read and
                # quantize fall behind the device
                with _TRACER.span("bulk/prefetch_wait", "bulk"):
                    span, payload = next(chunks)
                t0 = time.perf_counter()
                outs = {}
                # covers dispatch only (jax is async): device compute
                # overlaps the next iteration; the wait for it is under
                # the chunk's bulk/sync span
                with _chunk_span("bulk/score", span, padded=span.padded,
                                 models=len(self.plans)):
                    for name, plan in self.plans.items():
                        g = self._group_of[name]
                        x_in = payload[g.fingerprint if g.use_pool
                                       else "__float__"]
                        outs[name] = self._score_entry(plan, x_in)
                pending.append((span, outs, t0))
                if len(pending) > 1:
                    drain(pending.pop(0))
            while pending:
                drain(pending.pop(0))
        finally:
            if isinstance(stream, Prefetcher):
                stream.close()
        metrics.stop()
        metrics.compiles = sum(p.stats["total_traces"]
                               for p in self.plans.values()) - traces0

        outputs = {name: sinks[name].close() for name in self.plans}
        return ScoreResult(outputs=outputs, metrics=metrics.snapshot(),
                           chunk_rows=chunk_rows,
                           chunk_shapes=tuple(sorted(
                               {s.padded for s in todo})),
                           n_rows=n_rows)

    def _normalize_sinks(self, sinks) -> dict[str, ScoreSink]:
        if sinks is None:
            return {name: ArraySink() for name in self.plans}
        if isinstance(sinks, Mapping):
            missing = set(self.plans) - set(sinks)
            if missing:
                raise ValueError(f"no sink for plans {sorted(missing)}")
            return {name: sinks[name] for name in self.plans}
        if len(self.plans) != 1:
            raise ValueError("a single bare sink needs a single plan; "
                             f"got plans {sorted(self.plans)}")
        return {next(iter(self.plans)): sinks}
