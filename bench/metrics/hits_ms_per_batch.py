"""Device milliseconds of the store's hits program per served batch,
over the executions wholly inside the traced window (moves
``query_p95_ms``)."""
from bench import names


def read(run):
    t = run.trace
    n = t.runs(names.HITS)
    return 1e3 * t.program_seconds(names.HITS) / n if n else None
