"""Mean valid rows per device call in the window: the server's request
count over its batch count (`ServerMetrics`)."""


def read(run):
    batches = run.counters.get("batches")
    if not batches:
        return None
    return run.counters["requests"] / batches
