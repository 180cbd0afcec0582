"""The stand-in graph with its vertex ids relabelled by a seeded
permutation.

R-MAT names a vertex by its quadrant choices, so the vertices with the
most edges get the lowest ids (folded by ``% n`` in `bench.data`).  On a
layout that cuts the vertex ids into equal blocks, that puts most edges
into the first block: a skew across partitions that the generator makes
and no published graph states.  Graph500's Kronecker generator permutes
its vertex labels for the same reason.

`make_edges` is `bench.data.make_edges` with every vertex id ``v``
renamed ``perm[v]``, where ``perm`` is a permutation of ``range(n)``
drawn from the configuration's ``graph.seed`` (a stream of its own, so
the edges and weights drawn are those of `bench.data`).  Each edge keeps
its IC probability and LT weight, and the edges are sorted by (src,
dst) again: the graph is the same up to the names of its vertices.  Both
the program and the plain reference are given the relabelled edges.
"""
from __future__ import annotations

import numpy as np

from bench import data


def relabel(e: data.Edges, rng) -> data.Edges:
    """``e`` with its vertex ids permuted by ``rng.permutation(n)``."""
    perm = rng.permutation(e.n).astype(np.int64)
    src, dst = perm[e.src], perm[e.dst]
    order = np.argsort(src * e.n + dst)
    return data.Edges(e.n, src[order].astype(np.int32),
                      dst[order].astype(np.int32), e.prob[order],
                      e.lt[order])


def make_edges(config: dict) -> data.Edges:
    """The configuration's graph and weights, vertex ids relabelled."""
    return relabel(data.make_edges(config),
                   data.streams(int(config["graph"]["seed"]), 4)[3])
