"""CSR edges the sparse traversal walks a step over the edges there
are: the ``walked`` (the edges all devices of one theta shard walk, a
window per vertex block) over the ``owned`` (m) of the meshed ``sample``
spans that start inside the traced window (moves ``rrr_sets_per_s``).
1 is a split in which each vertex block's window holds its own
out-edges and no more."""
from bench import spans


def read(run):
    ev = spans.in_window(run, "sample", "walked", "owned")
    return spans.ratio([e["args"]["walked"] for e in ev],
                       [e["args"]["owned"] for e in ev])
