"""Fenced binarize time per chunk on the prefetch worker, in ms: the
scorer's `quantize_s` over its chunk count, summed over the window's
sweeps (`ScoringMetrics`)."""


def read(run):
    chunks = run.counters.get("chunks")
    if not chunks:
        return None
    return 1e3 * run.counters["quantize_s"] / chunks
