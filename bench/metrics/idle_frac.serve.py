"""Share of the traced window in which no operation ran on the device,
in a serve cell (moves ``query_p95_ms``)."""


def read(run):
    return 100.0 * run.trace.idle_frac()
