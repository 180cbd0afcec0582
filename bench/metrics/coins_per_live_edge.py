"""Coins the IC traversal draws per (row, edge) pair it consults: the
``coins`` (steps x batch x m) over the ``consulted`` pairs
(colsum . in-degree) of the ``sample`` spans that start inside the
traced window (moves ``rrr_sets_per_s``).  1 is a sampler that draws
coins for live edges only."""
from bench import spans


def read(run):
    ev = spans.in_window(run, "sample", "coins", "consulted")
    return spans.ratio([e["args"]["coins"] for e in ev],
                       [e["args"]["consulted"] for e in ev])
