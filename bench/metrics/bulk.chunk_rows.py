"""The chunk shape the scorer's planner chose (`ScoreResult.chunk_rows`)."""


def read(run):
    return run.counters.get("chunk_rows")
