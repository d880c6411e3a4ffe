"""node_index's device time per chunk of the traced sweep, in ms: the
summed duration of its op events (matched as node_index_roofline
matches them) over the sweep's chunks, its rows over the scorer's chunk
rows.  Beside leaf_gather's share of the breakdown, it shows how the
node-table stage and the shared leaf sum divide the step."""
import math

from harness import spec


def read(run):
    chunk = run.counters.get("chunk_rows")
    if not chunk or run.traced_rows <= 0:
        return None
    roofline = spec.load_module(
        run.bench_dir / "metrics" / "node_index_roofline.py",
        "bench_metric_node_index_roofline")
    hits = roofline.node_index_events(run)
    if not hits:
        return None
    return 1e-6 * sum(e.dur_ns for e in hits) / math.ceil(
        run.traced_rows / chunk)
