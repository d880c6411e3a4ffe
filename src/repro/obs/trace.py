"""Span tracer with Chrome-trace-event export.

The paper attributes its speedups loop-by-loop (Tables 2-6 time each
vectorized hotspot separately on the Lichee Pi 4a); this module is the
same attribution for our stack: "where did this 180ms batch go?" is
answered by loading `trace.export_chrome(path)` output into Perfetto
(https://ui.perfetto.dev) or chrome://tracing and reading the timeline.

Design constraints, in priority order:

1. **On the profiler's clock.**  Every `span()` also enters a
   `jax.profiler.TraceAnnotation` of the same name, whether or not the
   ring is enabled.  With no profiler session running it is an inert
   TraceMe (about 0.2 us per enter and exit on a CPU host); under
   `jax.profiler.start_trace` the span lands on the host thread's line
   of the profiler trace, on the same clock as the device's ops, so an
   idle gap of the device can be set against what each host thread was
   doing.  Span attributes never reach the annotation.
2. **Near-zero overhead when the ring is disabled.**  The ring defaults
   OFF; a disabled `span()` is the bare annotation, with no attribute
   dict and nothing recorded, and hot sites guard with
   `if TRACER.enabled:` before building any other arguments.  The
   disabled-cost bound is asserted in tests/test_obs.py.
3. **Thread-safe, bounded memory.**  Events land in a
   `collections.deque(maxlen=capacity)` ring buffer — appends are
   atomic under the GIL, eviction is FIFO (oldest events drop first),
   and a runaway trace can never grow past `capacity` events.
4. **Monotonic clocks.**  Ring timestamps come from
   `time.perf_counter_ns` relative to the tracer's epoch; wall-clock
   adjustments can never produce negative durations.  `complete()` and
   `instant()` are ring-only: the profiler cannot take a backdated or
   zero-length annotation.

Event kinds (Chrome trace `ph` values the exporter emits):

  span     `ph="X"` complete event: name, category, ts, dur, args —
           produced by the `span()` context manager
  instant  `ph="i"` instant event — e.g. Predictor compile events
  counter  `ph="C"` counter event — a sampled value over time
  (plus `ph="M"` thread-name metadata rows, emitted at export time)

Span taxonomy (see docs/observability.md for the full contract):

  compile/<entry>      Predictor XLA trace (entry, layout, batch rows)
  sharded/<kind>       mesh-sharded predict (shard axis, device count)
  bulk/read            BulkScorer source read of a chunk (prefetch worker)
  bulk/quantize        pad + binarize dispatch, no fence (prefetch worker)
  bulk/quantize_wait   the binarize fence (prefetch worker)
  bulk/prefetch_wait   main thread blocked on the prefetch queue
  bulk/score           BulkScorer chunk dispatch (main thread)
  bulk/sync            device -> host copy of the lag-1 chunk (main)
  bulk/sink            sink write of the lag-1 chunk (main)
  train/level          GBDTTrainer per-level histogram+split pass
  train/iteration      GBDTTrainer whole boosting iteration
  serve/batch          GBDTServer scored batch
"""
from __future__ import annotations

import collections
import json
import pathlib
import threading
import time
from typing import Any, Optional

from jax.profiler import TraceAnnotation

DEFAULT_CAPACITY = 65536


class _AnnotatedSpan(TraceAnnotation):
    """The span `span()` returns while the ring is disabled: the bare
    profiler annotation, recording nothing in the ring."""

    __slots__ = ()

    def set(self, **attrs: Any) -> None:
        """Attribute updates on a disabled span are dropped."""


class _Span:
    """A live span: enters the profiler annotation and records ts on
    __enter__, appends to the ring on __exit__."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0
        self._annotation = TraceAnnotation(name)

    def __enter__(self) -> "_Span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> bool:
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._tracer._append(("X", self.name, self.cat, self._t0,
                              t1 - self._t0,
                              threading.get_ident(), self.args))
        return False

    def set(self, **attrs: Any) -> None:
        """Attach attributes discovered mid-span (e.g. result shape)."""
        self.args.update(attrs)


class Tracer:
    """Thread-safe span/instant/counter recorder with a bounded ring.

    One process-wide instance (`get_tracer()`) serves every
    instrumentation site; tests may construct private tracers.  All
    recording methods are safe to call from any thread — the scorer's
    prefetch worker and the serving batcher thread record into the
    same ring as the main thread, which is exactly what makes prefetch
    overlap visible on the exported timeline.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.enabled = False
        # (ph, name, cat, t_ns, dur_ns, thread_ident, args) tuples.
        # deque.append is atomic under the GIL and maxlen gives FIFO
        # eviction — no lock on the record path.
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._epoch_ns = time.perf_counter_ns()
        self._lock = threading.Lock()
        self._dropped = 0
        # thread ident -> name, captured at record time: a worker (the
        # scorer's Prefetcher) may be gone by export time, when
        # threading.enumerate() can no longer name it
        self._thread_names: dict[int, str] = {}

    # -- control -----------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        self._ring.clear()
        self._epoch_ns = time.perf_counter_ns()
        self._dropped = 0

    # -- recording ---------------------------------------------------------
    def _append(self, event: tuple) -> None:
        if len(self._ring) == self.capacity:
            # racy read, but the count is advisory (exported as
            # metadata); the ring itself evicts correctly regardless
            self._dropped += 1
        # by the latest thread to record under this ident: the OS hands
        # a dead thread's ident to the next thread it starts
        self._thread_names[event[5]] = threading.current_thread().name
        self._ring.append(event)

    def span(self, name: str, cat: str = "", **attrs: Any):
        """Context manager timing a region on the profiler's clock and,
        while the ring is enabled, in the ring.  Use it on the thread
        that does the work: an annotation opens and closes on one
        thread."""
        if not self.enabled:
            return _AnnotatedSpan(name)
        return _Span(self, name, cat, attrs)

    def complete(self, name: str, cat: str = "", *, start_ns: int,
                 duration_ns: int, **attrs: Any) -> None:
        """Record an already-timed region as a complete span.

        For call sites that measure their own stage timings anyway
        (the trainer's per-level clocks): `start_ns` is a
        `time.perf_counter_ns()` reading — the same clock spans use —
        so these land on the timeline exactly like `span()` output."""
        if not self.enabled:
            return
        self._append(("X", name, cat, start_ns, duration_ns,
                      threading.get_ident(), attrs))

    def instant(self, name: str, cat: str = "", **attrs: Any) -> None:
        """A point-in-time event (Chrome `ph="i"`)."""
        if not self.enabled:
            return
        self._append(("i", name, cat, time.perf_counter_ns(), 0,
                      threading.get_ident(), attrs))

    def counter(self, name: str, cat: str = "",
                **values: float) -> None:
        """A process-level counter sample (Chrome `ph="C"` — renders
        as a stacked area track).  Values must be numeric."""
        if not self.enabled:
            return
        self._append(("C", name, cat, time.perf_counter_ns(), 0,
                      threading.get_ident(), values))

    # -- reading -----------------------------------------------------------
    def events(self) -> list[dict[str, Any]]:
        """Snapshot of the ring as dicts (oldest first).  Timestamps
        are microseconds relative to the tracer epoch."""
        epoch = self._epoch_ns
        out = []
        for ph, name, cat, t_ns, dur_ns, tid, args in list(self._ring):
            out.append({"ph": ph, "name": name, "cat": cat,
                        "ts_us": (t_ns - epoch) / 1e3,
                        "dur_us": dur_ns / 1e3, "tid": tid,
                        "args": dict(args)})
        return out

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def dropped(self) -> int:
        """Events evicted by the ring bound (advisory count)."""
        return self._dropped

    # -- export ------------------------------------------------------------
    def export_chrome(self, path: str | pathlib.Path) -> dict[str, Any]:
        """Write the ring as Chrome trace-event JSON and return the
        object.  The file loads directly in Perfetto or
        chrome://tracing: spans are `ph="X"` complete events with
        microsecond `ts`/`dur`, counters are `ph="C"`, and thread-name
        metadata rows label the prefetch/batcher worker threads so
        overlap is readable."""
        with self._lock:
            events = list(self._ring)
            epoch = self._epoch_ns
            dropped = self._dropped
            names = dict(self._thread_names)
        pid = 1
        tid_map: dict[int, int] = {}
        rows: list[dict[str, Any]] = []
        main_ident = threading.main_thread().ident
        for ph, name, cat, t_ns, dur_ns, tid, args in events:
            if tid not in tid_map:
                tid_map[tid] = len(tid_map)
                label = ("main" if tid == main_ident
                         else names.get(tid, f"thread-{len(tid_map)}"))
                rows.append({"ph": "M", "name": "thread_name", "pid": pid,
                             "tid": tid_map[tid],
                             "args": {"name": label}})
            row: dict[str, Any] = {
                "ph": ph, "name": name, "cat": cat or "repro",
                "ts": (t_ns - epoch) / 1e3, "pid": pid,
                "tid": tid_map[tid], "args": dict(args),
            }
            if ph == "X":
                row["dur"] = dur_ns / 1e3
            elif ph == "i":
                row["s"] = "t"           # instant scope: thread
            rows.append(row)
        obj = {"traceEvents": rows, "displayTimeUnit": "ms",
               "otherData": {"dropped_events": dropped,
                             "capacity": self.capacity}}
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(obj))
        return obj


# --------------------------------------------------------------------------
# Process-wide tracer + module-level conveniences
# --------------------------------------------------------------------------
_GLOBAL = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer every instrumentation site records to."""
    return _GLOBAL


def enabled() -> bool:
    return _GLOBAL.enabled


def enable() -> None:
    _GLOBAL.enable()


def disable() -> None:
    _GLOBAL.disable()


def span(name: str, cat: str = "", **attrs: Any):
    return _GLOBAL.span(name, cat, **attrs)


def instant(name: str, cat: str = "", **attrs: Any) -> None:
    _GLOBAL.instant(name, cat, **attrs)


def counter(name: str, cat: str = "", **values: float) -> None:
    _GLOBAL.counter(name, cat, **values)


def export_chrome(path: str | pathlib.Path) -> dict[str, Any]:
    return _GLOBAL.export_chrome(path)


class tracing:
    """`with tracing():` — enable the global tracer for a region and
    restore the previous state on exit (exception-safe; what the CLI
    `--trace-out` flags use)."""

    def __init__(self, tracer: Optional[Tracer] = None,
                 clear: bool = False):
        # explicit None test: an *empty* Tracer is falsy (__len__ == 0)
        self._tracer = tracer if tracer is not None else _GLOBAL
        self._clear = clear
        self._was = False

    def __enter__(self) -> Tracer:
        if self._clear:
            self._tracer.clear()
        self._was = self._tracer.enabled
        self._tracer.enable()
        return self._tracer

    def __exit__(self, *exc: Any) -> bool:
        self._tracer.enabled = self._was
        return False
