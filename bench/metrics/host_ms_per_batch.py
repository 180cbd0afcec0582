"""Host milliseconds per batch in the engine's ``extend``: the duration
of the ``extend`` spans over the ``batches`` they issued, those that
start inside the traced window (moves ``rrr_sets_per_s``).  The device
works asynchronously, so this is the host's dispatch and bookkeeping,
tracing's own cost included."""
from bench import spans


def read(run):
    ev = spans.in_window(run, "extend", "batches")
    r = spans.ratio([e["dur"] for e in ev],
                    [e["args"]["batches"] for e in ev])
    return None if r is None else r / 1e3
