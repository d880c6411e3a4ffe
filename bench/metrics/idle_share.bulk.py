"""Device idle share of the traced slice, in %: 1 - busy / window, busy
being the union of the device's op intervals (bench/harness/trace.py)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
