"""The relabelled stand-in (`bench.relabel`): the same graph as
`bench.data.make_edges` up to the names of its vertices, drawn the same
on every call, with its edges spread over equal vertex blocks where
R-MAT's own numbering piles them into the first."""
import numpy as np
import pytest

from bench import data, relabel


def config(n=4999, undirected=15000, seed=0):
    return {"graph": {"n": n, "undirected_edges": undirected,
                      "directed": False, "seed": seed}}


def edge_map(e):
    """(src, dst) -> (IC probability, LT weight) for every edge."""
    return {(int(s), int(d)): (float(p), float(w))
            for s, d, p, w in zip(e.src, e.dst, e.prob, e.lt)}


def test_it_is_the_same_graph_with_its_ids_renamed():
    cfg = config()
    e, r = data.make_edges(cfg), relabel.make_edges(cfg)
    assert r.n == e.n and r.src.dtype == r.dst.dtype == np.int32
    perm = data.streams(0, 4)[3].permutation(e.n)
    assert sorted(perm.tolist()) == list(range(e.n))
    want = {(int(perm[s]), int(perm[d])): v
            for (s, d), v in edge_map(e).items()}
    assert edge_map(r) == want
    key = r.src.astype(np.int64) * r.n + r.dst
    assert np.all(np.diff(key) > 0)          # sorted by (src, dst), no repeat
    assert np.array_equal(np.sort(np.bincount(e.src, minlength=e.n)),
                          np.sort(np.bincount(r.src, minlength=r.n)))


def test_it_is_drawn_from_the_graph_seed():
    a, b = relabel.make_edges(config()), relabel.make_edges(config())
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    assert np.array_equal(a.prob, b.prob)
    c = relabel.make_edges(config(seed=1))
    assert not np.array_equal(a.src, c.src)


@pytest.mark.parametrize("blocks", [2, 4])
def test_equal_blocks_hold_about_equal_edges(blocks):
    cfg = config(n=20000, undirected=60000)
    share = {}
    for name, e in (("raw", data.make_edges(cfg)),
                    ("relabelled", relabel.make_edges(cfg))):
        per = np.bincount(e.src // -(-e.n // blocks), minlength=blocks)
        share[name] = per.max() * blocks / e.src.size
    assert share["raw"] > 1.5
    assert share["relabelled"] < 1.1
