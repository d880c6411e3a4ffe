"""Model operations of the valid rows served in the traced slice, over
the device's busy time in that slice x the chip's peak op rate, in %.
Busy time is the denominator because the offered rate, fixed by the
cell, fixes the window's rate."""


def read(run):
    rows = run.counters.get("traced_valid_rows")
    if run.trace is None or not rows or run.trace.busy_s <= 0:
        return None
    ops = run.ops_per_row(("fused_predict",)) * rows
    return 100.0 * ops / (run.trace.busy_s * run.peaks["ops_per_s"])
