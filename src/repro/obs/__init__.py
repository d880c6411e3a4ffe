"""Unified telemetry: stage spans on the profiler's clock + metrics
aggregation.

The paper's whole argument is a measurement story — it attributes the
RISC-V speedups loop-by-loop by benchmarking each vectorized kernel on
real hardware.  This package is that attribution layer for our stack:

* `repro.obs.trace` — a thread-safe span tracer whose spans are also
  `jax.profiler` annotations (so they share the device trace's clock),
  with a bounded ring, near-zero cost while the ring is off, and a
  Chrome-trace-event exporter (loadable in Perfetto / chrome://tracing).
  Instrumented: Predictor compile events, the BulkScorer's read /
  quantize / wait / score / sync / sink stages (prefetch overlap
  visible on the timeline), per-level training histogram passes,
  sharded mesh entries, served batches.
* `repro.obs.hub` — a `MetricsHub` that registers the existing
  `ServerMetrics` / `ScoringMetrics` / `TrainingMetrics` snapshots
  behind one namespace and exports Prometheus-textfile and JSON
  formats; serving snapshots carry deadline-SLO accounting.

See docs/observability.md for the span taxonomy and exporter formats.
"""
from repro.obs.trace import (Tracer, get_tracer, span, instant, counter,
                             enable, disable, enabled,
                             export_chrome)   # noqa: F401
from repro.obs.hub import MetricsHub          # noqa: F401
