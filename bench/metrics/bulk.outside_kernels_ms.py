"""Device time per chunk of the traced slice's ops that belong to no
kernel of bench/kernels/ (relayout copies, pads, slices, softmax), in
ms (bench/harness/spans.py).  Chunks: the traced sweep's rows over the
scorer's chunk rows, one per `bulk/score` span."""
import math

from harness import spans, spec


def read(run):
    chunk = run.counters.get("chunk_rows")
    if run.trace is None or not chunk or run.traced_rows <= 0:
        return None
    patterns = [p for k in spec.kernel_names(run.bench_dir)
                for p in spec.kernel(run.bench_dir, k).EVENTS]
    n = math.ceil(run.traced_rows / chunk)
    return 1e3 * spans.outside_s(run.trace.ops, patterns) / n
