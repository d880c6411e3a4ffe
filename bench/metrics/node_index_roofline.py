"""node_index's share of its roofline in the traced slice, in %: the
least time (the larger of ops / peak op rate and bytes / peak HBM
bandwidth, bench/kernels/node_index.py over the run's dims and its
`nodes_per_tree` counter) over the kernel's summed device time.

Op events are matched on their instruction's own name
(`spans.op_name`): a raw "XLA Ops" name is HLO text that begins with
"%", which `trace.matches` alone does not see past."""
from harness import spans, spec, trace


def node_index_events(run):
    """The traced slice's node_index op events; [] without a trace."""
    if run.trace is None:
        return []
    events = spec.kernel(run.bench_dir, "node_index").EVENTS
    return [e for e in run.trace.ops
            if trace.matches(spans.op_name(e.name), events)]


def read(run):
    hits = node_index_events(run)
    secs = sum(e.dur_ns for e in hits) * 1e-9
    if not hits or secs <= 0 or run.traced_rows <= 0 or not run.peaks:
        return None
    dims = {**run.dims, "nodes_per_tree": run.counters.get("nodes_per_tree")}
    ops, nbytes = spec.kernel(run.bench_dir, "node_index").work(
        dims, run.traced_rows, len(hits))
    least = max(ops / run.peaks["ops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
