"""Model operations per row (binarize + leaf index + leaf sum, counted
by bench/kernels/) x rows_per_s of the run's window, over the chip's
peak op rate, in %."""

KERNELS = ("binarize", "leaf_index", "leaf_gather")


def read(run):
    rate = run.counters.get("rows_per_s")
    if not rate or not run.peaks:
        return None
    return 100.0 * run.ops_per_row(KERNELS) * rate / run.peaks["ops_per_s"]
