"""Bytes the RRR store moves per set committed: the ``bytes`` of the
``store.write`` and ``store.grow`` spans over the ``sets`` of the
``store.write`` spans, those that start inside the traced window
(moves ``rrr_sets_per_s``).  The commit alone is about n + 4 bytes a
set; growth copies and fills add the rest."""
from bench import spans


def read(run):
    writes = spans.in_window(run, "store.write", "sets", "bytes")
    grows = spans.in_window(run, "store.grow", "bytes")
    return spans.ratio([e["args"]["bytes"] for e in writes + grows],
                       [e["args"]["sets"] for e in writes])
