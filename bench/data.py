"""Inputs made from ``--seed``: the graph stand-in and its edge weights.

The R-MAT arithmetic is a copy of the program's
``repro.graphs.generators.rmat_graph`` (quadrant choices, self-loop drop,
both directions for an undirected graph, de-duplication), so that a
later change to the program cannot change the yardstick's graph.  Unlike
the program's, it draws until the graph has the configuration's edge
count exactly (``undirected_edges``, or ``edges`` for a directed graph):
de-duplication would otherwise leave it short.  The
weights follow the configuration's rule:

* ``uniform`` (IC): one U(0, 1) probability per directed edge;
* ``lt_normalised`` (LT): one U(0, 1) draw per edge, scaled per
  destination so that the in-weights of a vertex with in-edges sum to a
  U(0.3, 1) total (the repository's LT rule).

The graph and its weights are the deployment's data: they come from the
configuration's own ``graph.seed``, the same for every run, so that each
``--seed`` samples the same network (``--seed`` draws the engine's
coins, the rows checked and the traffic).  Every stream comes from its
own child of ``SeedSequence(seed)``, so any whole number is a seed.

The CSC view (`Csc`) is what the plain references walk; it is computed
here from the same edge list, by the rule the program documents: a
stable sort by destination.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_RMAT_ABC = (0.57, 0.19, 0.19)


def streams(seed: int, count: int) -> list:
    """``count`` independent numpy generators derived from ``seed``."""
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(int(seed)).spawn(count)]


def rmat_edges(n: int, edges: int, directed: bool, rng) -> tuple:
    """(src, dst) int32 edge arrays of an R-MAT graph with n vertices and
    exactly ``edges`` distinct edges (vertex pairs when undirected, both
    directions stored), sorted by (src, dst).  Self-loops and repeats are
    dropped and more edges drawn while any are missing; the first
    ``edges`` distinct ones, in the order drawn, are kept."""
    a, b, c = _RMAT_ABC
    scale = int(np.ceil(np.log2(max(n, 2))))
    probs = np.array([a, b, c, 1.0 - a - b - c])
    weights = (1 << np.arange(scale - 1, -1, -1)).astype(np.int64)
    have = np.zeros(0, np.int64)
    while have.size < edges:
        short = edges - have.size
        quad = rng.choice(4, size=(short + short // 16 + 64, scale),
                          p=probs)
        row_bits = (quad == 2) | (quad == 3)
        col_bits = (quad == 1) | (quad == 3)
        src = (row_bits @ weights) % n
        dst = (col_bits @ weights) % n
        keep = src != dst
        src, dst = src[keep], dst[keep]
        if not directed:
            src, dst = np.minimum(src, dst), np.maximum(src, dst)
        eid = src * n + dst
        _, first = np.unique(eid, return_index=True)
        eid = eid[np.sort(first)]
        have = np.concatenate([have, eid[~np.isin(eid, have)]])
    have = have[:edges]
    src, dst = have // n, have % n
    if not directed:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    order = np.argsort(src * n + dst)
    return src[order].astype(np.int32), dst[order].astype(np.int32)


def lt_weights(dst, n: int, rng) -> np.ndarray:
    """Per-edge LT weights (aligned with ``dst``): U(0, 1) draws scaled
    so each vertex with in-edges gets a U(0.3, 1) total."""
    raw = rng.uniform(0.0, 1.0, size=dst.shape[0])
    seg = np.bincount(dst, weights=raw, minlength=n)
    total = rng.uniform(0.3, 1.0, size=n)
    scale = np.where(seg > 0, total / np.maximum(seg, 1e-30), 0.0)
    return raw * scale[dst]


@dataclasses.dataclass(frozen=True)
class Edges:
    """One seeded graph as the benchmark made it (input edge order)."""
    n: int
    src: np.ndarray
    dst: np.ndarray
    prob: np.ndarray        # float32 IC probability per edge
    lt: np.ndarray          # float64 LT weight per edge


def make_edges(config: dict) -> Edges:
    """The configuration's graph and weights (from its ``graph.seed``)."""
    g = config["graph"]
    r_graph, r_prob, r_lt = streams(int(g["seed"]), 3)
    count = g["edges"] if g["directed"] else g["undirected_edges"]
    src, dst = rmat_edges(int(g["n"]), int(count), bool(g["directed"]),
                          r_graph)
    prob = r_prob.uniform(0.0, 1.0, size=src.shape[0]).astype(np.float32)
    lt = lt_weights(dst, int(g["n"]), r_lt)
    return Edges(int(g["n"]), src, dst, prob, lt)


def program_graph(e: Edges):
    """The program's `Graph` for these edges (its only input besides
    the engine's configuration)."""
    from repro.graphs.csr import build_graph
    return build_graph(e.src, e.dst, e.n, ic_prob=e.prob, lt_weight=e.lt)


@dataclasses.dataclass(frozen=True)
class Csc:
    """In-edges grouped by destination, the order in which the engine
    numbers its per-edge coins."""
    n: int
    off: np.ndarray         # (n + 1,) int64 segment offsets
    src: np.ndarray         # (m,) int32 source of each in-edge
    prob: np.ndarray        # (m,) float32 IC probability
    cum: np.ndarray         # (m,) float32 within-segment cumulative LT weight
    total: np.ndarray       # (n,) float32 LT weight total per vertex

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    def in_edges(self, verts: np.ndarray) -> np.ndarray:
        """CSC positions of every in-edge of ``verts``."""
        lo, hi = self.off[verts], self.off[verts + 1]
        cnt = hi - lo
        if not cnt.sum():
            return np.zeros(0, np.int64)
        starts = np.repeat(lo - np.concatenate([[0], np.cumsum(cnt)[:-1]]),
                           cnt)
        return starts + np.arange(cnt.sum())


def csc(e: Edges) -> Csc:
    """CSC view of ``e``.  The LT cumulative weights are a running
    float64 sum over the CSC order less the sum at the segment's start,
    rounded to float32; totals are float64 sums rounded to float32."""
    order = np.argsort(e.dst, kind="stable")
    dst = e.dst[order]
    off = np.concatenate([[0], np.cumsum(np.bincount(dst, minlength=e.n))])
    w = e.lt[order]
    run = np.cumsum(w)
    start = np.concatenate([[0.0], run])[off[:-1]]
    cum = (run - start[dst]).astype(np.float32)
    total = np.zeros(e.n)
    np.add.at(total, dst, w)
    return Csc(e.n, off.astype(np.int64), e.src[order], e.prob[order], cum,
               total.astype(np.float32))
