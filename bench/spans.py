"""The program's obs spans that start inside a run's traced window.

The program stamps its spans (``repro.obs``) in microseconds on its own
clock; the reduced trace (`bench.trace`) keeps its window, and the
annotations the spans were bridged into (``Trace.host``), in
nanoseconds from the profile's start.  The two clocks tick together, so
one offset places every span on the trace's timeline: the one at which
the program's ``extend`` spans line up with their annotations.  Each
annotation opens a few microseconds before its span reads the clock (a
garbage collection in between can add milliseconds, never take any
away), so a span's start less its annotation's is the offset plus a
small delay.  Of the alignments of the trace's K annotations with K
consecutive spans, the true one puts the median of these differences
within microseconds of their least, and any other by the jitter of the
gaps between batches; the narrowest is taken, if under `TOLERANCE_NS`,
and its least difference is the offset.

A reader built on this finds nothing, and returns None, where the trace
holds no ``extend`` annotation or the spans carry none of its arguments.
"""
import numpy as np

ANCHOR = "extend"
TOLERANCE_NS = 1e6


def _spread(d):
    return np.median(d) - d.min()


def offset_ns(run):
    """What to take from a span's ``ts * 1e3`` to place it on the
    trace's timeline, or None where no alignment holds."""
    marks = np.sort([s for n, s, _ in run.trace.host if n == ANCHOR])
    ts = np.sort([ev["ts"] * 1e3 for ev in run.spans
                  if ev.get("name") == ANCHOR])
    k = len(marks)
    if not k or len(ts) < k:
        return None
    best = min((ts[j:j + k] - marks for j in range(len(ts) - k + 1)),
               key=_spread)
    return float(best.min()) if _spread(best) < TOLERANCE_NS else None


def in_window(run, name: str, *args: str) -> list:
    """The ``name`` spans that start inside the traced window and carry
    every argument in ``args``, as Chrome trace events."""
    off = offset_ns(run)
    if off is None:
        return []
    t = run.trace
    return [ev for ev in run.spans
            if ev.get("name") == name
            and t.t0 <= ev["ts"] * 1e3 - off < t.t1
            and all(a in ev.get("args", {}) for a in args)]


def ratio(num: list, den: list):
    """Sum over sum, or None where the denominator sums to 0."""
    d = sum(den)
    return sum(num) / d if d else None
