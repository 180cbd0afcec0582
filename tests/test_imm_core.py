"""IMM core: martingale bounds, samplers, selection, Algorithm-1 driver."""
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import martingale as mg
from repro.core.imm import imm, IMMConfig
from repro.core.sampler import (
    make_logq, sample_ic_dense, sample_ic_sparse, sample_lt,
)
from repro.core.selection import select_dense, select_sparse
from repro.core.adaptive import (
    choose_representation, bitmap_to_indices, indices_to_bitmap,
)
from repro.graphs import star_graph, path_graph, rmat_graph, erdos_graph


# ------------------------------------------------------------ martingale ----

def test_bounds_monotone_in_eps():
    b1 = mg.compute_bounds(10_000, 50, 0.5)
    b2 = mg.compute_bounds(10_000, 50, 0.25)
    assert b2.lam_prime > b1.lam_prime       # smaller eps -> more samples
    assert b2.lam_star > b1.lam_star


def test_round_theta_doubles():
    b = mg.compute_bounds(10_000, 50, 0.5)
    assert mg.round_theta(b, 2) == pytest.approx(
        2 * mg.round_theta(b, 1), rel=0.01)


def test_theta_from_lb_decreases_with_lb():
    b = mg.compute_bounds(10_000, 50, 0.5)
    assert mg.theta_from_lb(b, 1000.0) < mg.theta_from_lb(b, 100.0)


def test_tang15_formula_spotcheck():
    """lambda' literal recomputation (Tang'15 Eq. in §4.2)."""
    n, k, eps = 1000, 10, 0.5
    b = mg.compute_bounds(n, k, eps)
    ell = 1.0 * (1 + math.log(2) / math.log(n))
    epsp = math.sqrt(2) * eps
    expect = ((2 + 2 / 3 * epsp)
              * (mg.log_comb(n, k) + ell * math.log(n)
                 + math.log(max(math.log2(n), 1)))
              * n / epsp ** 2)
    assert b.lam_prime == pytest.approx(expect, rel=1e-9)


# -------------------------------------------------------------- samplers ----

def test_ic_dense_star_closed_form():
    """Star 0->i with prob p: RRR(root=i) contains 0 w.p. p."""
    p = 0.7
    g = star_graph(64, p=p)
    logq = make_logq(g)
    hits, tot = 0, 0
    for s in range(6):
        visited, counter, roots = sample_ic_dense(
            jax.random.PRNGKey(s), logq, batch=512)
        spoke = np.asarray(roots) != 0
        hits += int(np.asarray(visited)[spoke, 0].sum())
        tot += int(spoke.sum())
    assert hits / tot == pytest.approx(p, abs=0.03)


def test_ic_dense_vs_sparse_distribution():
    """Dense (log-semiring) and sparse (per-edge coin) samplers agree in
    expected RRR size on the same graph."""
    g = rmat_graph(128, 1024, seed=3)
    logq = make_logq(g)
    v1, c1, _ = sample_ic_dense(jax.random.PRNGKey(0), logq, batch=1024)
    v2, c2, _ = sample_ic_sparse(
        jax.random.PRNGKey(1), g.edge_src, g.edge_dst, g.in_prob,
        n_nodes=g.n, batch=1024)
    s1 = float(np.asarray(v1).sum(1).mean())
    s2 = float(np.asarray(v2).sum(1).mean())
    assert s1 == pytest.approx(s2, rel=0.12), (s1, s2)


def test_ic_sparse_path_reachability():
    """Path 0->1->...->n-1 with p=1: RRR(root) = {0..root}."""
    g = path_graph(16, p=1.0)
    visited, _, roots = sample_ic_sparse(
        jax.random.PRNGKey(0), g.edge_src, g.edge_dst, g.in_prob,
        n_nodes=g.n, batch=64)
    v = np.asarray(visited)
    r = np.asarray(roots)
    for b in range(64):
        expect = np.zeros(16, np.uint8)
        expect[: r[b] + 1] = 1
        np.testing.assert_array_equal(v[b], expect)


def test_lt_walk_is_path_and_counter_fused():
    g = rmat_graph(128, 1024, seed=4)
    visited, counter, roots = sample_lt(
        jax.random.PRNGKey(0), g.dst_offsets, g.in_src, g.in_lt_cum,
        g.in_lt_total, batch=256)
    v = np.asarray(visited)
    # root always in the set; counter equals fused column sums (paper C3)
    assert (v[np.arange(256), np.asarray(roots)] == 1).all()
    np.testing.assert_array_equal(np.asarray(counter), v.sum(0))


def test_rrrsets_contain_root_ic():
    g = rmat_graph(64, 256, seed=5)
    logq = make_logq(g)
    visited, _, roots = sample_ic_dense(jax.random.PRNGKey(2), logq,
                                        batch=128)
    v = np.asarray(visited)
    assert (v[np.arange(128), np.asarray(roots)] == 1).all()


# -------------------------------------------------------------- selection ----

def _numpy_greedy(R, valid, k):
    """Brute-force greedy max-coverage oracle."""
    R = np.asarray(R).astype(bool)
    alive = np.asarray(valid).copy()
    seeds, gains = [], []
    for _ in range(k):
        counter = R[alive].sum(axis=0)
        v = int(np.argmax(counter))
        covered = alive & R[:, v]
        seeds.append(v)
        gains.append(int(covered.sum()))
        alive = alive & ~R[:, v]
    return seeds, gains


@pytest.mark.parametrize("method", ["rebuild", "decrement"])
def test_select_dense_matches_numpy_greedy(method):
    rng = np.random.default_rng(0)
    R = (rng.random((80, 40)) < 0.2).astype(np.uint8)
    valid = np.ones(80, bool)
    valid[70:] = False
    seeds, frac, gains = select_dense(jnp.asarray(R), jnp.asarray(valid),
                                      5, method)
    ref_seeds, ref_gains = _numpy_greedy(R, valid, 5)
    np.testing.assert_array_equal(np.asarray(gains), ref_gains)
    # seeds may differ on argmax ties only; gains equality is the guarantee
    assert float(frac) == pytest.approx(sum(ref_gains) / 70.0)


def test_rebuild_equals_decrement():
    """Paper C5: the adaptive rebuild is algebraically identical to the
    decremental baseline."""
    rng = np.random.default_rng(1)
    R = (rng.random((120, 64)) < 0.15).astype(np.uint8)
    valid = jnp.ones((120,), bool)
    s1, f1, g1 = select_dense(jnp.asarray(R), valid, 8, "rebuild")
    s2, f2, g2 = select_dense(jnp.asarray(R), valid, 8, "decrement")
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    assert float(f1) == pytest.approx(float(f2))


def _rule_rows(R, valid, k):
    """Rows the adaptive update reads: every row once (the build), then
    after each pick but the last the smaller of the rows it newly
    covered (subtracted) and the rows alive after it (recounted), and
    which of the two each update took."""
    R = np.asarray(R).astype(bool)
    alive = np.asarray(valid).copy()
    rows, took = len(alive), []
    for i in range(k):
        v = int(np.argmax(R[alive].sum(axis=0)))
        covered = alive & R[:, v]
        alive &= ~covered
        if i < k - 1:
            c, a = int(covered.sum()), int(alive.sum())
            rows += min(c, a)
            took.append("subtract" if c <= a else "rebuild")
    return rows, took


def _arena(case):
    """(R, valid, k, the update branches the case must take)."""
    rng = np.random.default_rng(7)
    if case == "subtract":        # few rows a pick: always subtract
        R = rng.random((300, 90)) < 0.02
        return R, np.ones(300, bool), 12, {"subtract"}
    if case == "rebuild":         # vertex 3 holds 90% of the sets
        R = rng.random((200, 50)) < 0.1
        R[:, 3] = rng.random(200) < 0.9
        return R, np.ones(200, bool), 6, {"subtract", "rebuild"}
    if case == "masked":          # stale rows cleared, not a prefix
        R = rng.random((160, 70)) < 0.15
        return R, rng.random(160) < 0.6, 8, None
    if case == "exhausted":       # k past the covering vertices
        R = np.zeros((120, 40), bool)
        R[:, :5] = rng.random((120, 5)) < 0.3
        return R, np.ones(120, bool), 12, None
    if case == "ragged":          # theta off the 128-set tile, > 255 rows
        R = rng.random((700, 300)) < 0.4
        return R, np.ones(700, bool), 10, None
    raise ValueError(case)


@pytest.fixture(params=["oracle", "pallas"])
def kernels(request, monkeypatch):
    """The dense selection's kernels: the jnp oracles (what a CPU runs)
    or the Pallas kernels through the interpreter."""
    if request.param == "pallas":
        from functools import partial
        from repro.kernels import coverage_rows as kr, ops
        monkeypatch.setattr(ops, "row_view",
                            partial(kr.row_view, interpret=True))
        monkeypatch.setattr(ops, "coverage_rows",
                            partial(kr.coverage_rows, interpret=True))
        select_dense.clear_cache()
        yield request.param
        select_dense.clear_cache()
    else:
        yield request.param


@pytest.mark.parametrize("case", ["subtract", "rebuild", "masked",
                                  "exhausted", "ragged"])
def test_select_dense_adaptive_update(case, kernels):
    """C5's adaptive update picks what the plain greedy picks (seeds
    included: first index on ties) and what the decremental baseline
    picks, reading exactly the rows its rule names."""
    R, valid, k, branches = _arena(case)
    rows, took = _rule_rows(R, valid, k)
    if branches is not None:
        assert set(took) == branches
    Rj, vj = jnp.asarray(R.astype(np.uint8)), jnp.asarray(valid)
    got = select_dense(Rj, vj, k)
    seeds, frac, gains = got
    want_seeds, want_gains = _numpy_greedy(R, valid, k)
    np.testing.assert_array_equal(np.asarray(seeds), want_seeds)
    np.testing.assert_array_equal(np.asarray(gains), want_gains)
    d_seeds, d_frac, d_gains = select_dense(Rj, vj, k, "decrement")
    np.testing.assert_array_equal(np.asarray(seeds), np.asarray(d_seeds))
    np.testing.assert_array_equal(np.asarray(gains), np.asarray(d_gains))
    assert float(frac) == float(d_frac)
    assert int(got.rows) == rows
    if case == "exhausted":
        assert not np.asarray(gains)[5:].any()


def test_select_dense_rows_on_a_hand_built_arena(kernels):
    """Eight valid sets over four vertices (a ninth, invalid, holds all
    four).  The build reads all 9 sets.  Pick 1 (vertex 0) covers
    3 and leaves 5: subtract 3.  Pick 2 (vertex 1, first of a tie with
    vertex 3) covers 2 and leaves 3: subtract 2.  Pick 3 (vertex 3)
    covers 2 and leaves 1: recount 1.  Pick 4 is the last: no update."""
    R = np.zeros((9, 4), np.uint8)
    for s, v in enumerate([0, 0, 0, 1, 1, 2, 3, 3]):
        R[s, v] = 1
    R[8] = 1
    valid = np.arange(9) < 8
    got = select_dense(jnp.asarray(R), jnp.asarray(valid), 4)
    np.testing.assert_array_equal(np.asarray(got.seeds), [0, 1, 3, 2])
    np.testing.assert_array_equal(np.asarray(got.gains), [3, 2, 2, 1])
    assert int(got.rows) == 9 + 3 + 2 + 1
    assert _rule_rows(R, valid, 4) == (15, ["subtract", "subtract",
                                            "rebuild"])
    # the baseline counts the whole arena: the build and every pick
    assert int(select_dense(jnp.asarray(R), jnp.asarray(valid), 4,
                            "decrement").rows) == 5 * 9


def test_select_sparse_matches_dense():
    rng = np.random.default_rng(2)
    R = (rng.random((60, 32)) < 0.25).astype(np.uint8)
    valid = jnp.ones((60,), bool)
    R_idx = bitmap_to_indices(jnp.asarray(R), 16)
    sd, fd, gd = select_dense(jnp.asarray(R), valid, 4)
    ss, fs, gs = select_sparse(R_idx, valid, 32, 4)
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(gs))


def test_greedy_gains_non_increasing():
    """Submodularity: marginal gains decrease."""
    rng = np.random.default_rng(3)
    R = (rng.random((100, 50)) < 0.3).astype(np.uint8)
    _, _, gains = select_dense(jnp.asarray(R), jnp.ones((100,), bool), 10)
    g = np.asarray(gains)
    assert (g[:-1] >= g[1:]).all()


def _one_chip_forms(R):
    """Each one-chip at-rest form of the 0/1 arena ``R`` with the
    selection that reads it: ``layout -> select(valid, k, method)``."""
    from repro.core.adaptive import l_pad_for
    from repro.core.pack.codec import MIN_TOKEN_PAD, codec_for, tokens_needed
    from repro.core.pack.selection import select_compressed, select_packed
    n = R.shape[1]
    bits = jnp.asarray(R.astype(np.uint8))
    idx = bitmap_to_indices(bits, l_pad_for(int(R.sum(axis=1).max())))
    packed = codec_for("packed", n).encode(bits)
    s_pad = max(int(tokens_needed(bits).max()), MIN_TOKEN_PAD)
    tokens = codec_for("compressed", n, s_pad=s_pad).encode(bits)
    return {
        "dense": lambda v, k, m: select_dense(bits, v, k, m),
        "sparse": lambda v, k, m: select_sparse(idx, v, n, k, m),
        "packed": lambda v, k, m: select_packed(packed, v, n, k, m),
        "compressed": lambda v, k, m: select_compressed(tokens, v, n, k,
                                                        m),
    }


@pytest.mark.parametrize("method", ["rebuild", "decrement"])
@pytest.mark.parametrize("layout", ["dense", "sparse", "packed",
                                    "compressed"])
@pytest.mark.parametrize("arena", ["64x100", "513x300", "256x2000",
                                   "no_valid_row"])
def test_greedy_one_chip_forms_match_numpy_greedy(arena, layout, method):
    """Every one-chip at-rest form runs the one greedy loop to the plain
    greedy's seeds, gains and covered_frac, under both update rules.
    One vertex holds 90% of the sets, so the rebuild's first update
    recounts the survivors and the later ones subtract.
    ``no_valid_row`` is a full arena whose rows are all invalid: every
    pick gains nothing."""
    rng = np.random.default_rng(len(arena))
    if arena == "no_valid_row":
        R, valid = np.ones((32, 64), bool), np.zeros(32, bool)
    else:
        theta, n = map(int, arena.split("x"))
        R = rng.random((theta, n)) < 0.25
        R[:, n // 2] = rng.random(theta) < 0.9
        valid = rng.random(theta) < 0.8
    k = 8
    seeds, frac, gains = _one_chip_forms(R)[layout](
        jnp.asarray(valid), k, method)
    want_seeds, want_gains = _numpy_greedy(R, valid, k)
    np.testing.assert_array_equal(np.asarray(seeds), want_seeds)
    np.testing.assert_array_equal(np.asarray(gains), want_gains)
    want_frac = np.float32(sum(want_gains)) / np.float32(max(valid.sum(), 1))
    assert float(frac) == float(want_frac)


# -------------------------------------------------------------- adaptive ----

def test_bitmap_index_roundtrip():
    rng = np.random.default_rng(4)
    R = (rng.random((30, 25)) < 0.3).astype(np.uint8)
    l_max = int(R.sum(1).max())
    idx = bitmap_to_indices(jnp.asarray(R), l_max)
    R2 = indices_to_bitmap(idx, 25)
    np.testing.assert_array_equal(np.asarray(R2), R)


def test_choose_representation_thresholds():
    assert choose_representation(0.5, 1000, 100) == "bitmap"
    assert choose_representation(0.001, 100_000, 10) == "indices"
    # long index lists force bitmap regardless of coverage
    assert choose_representation(0.001, 1000, 900) == "bitmap"


# ------------------------------------------------------------ driver ----

@pytest.mark.parametrize("model", ["IC", "LT"])
def test_imm_end_to_end(model):
    g = rmat_graph(256, 2048, seed=1)
    res = imm(g, IMMConfig(k=5, model=model, batch=128, max_theta=1024))
    assert len(res.seeds) == 5
    assert len(set(int(s) for s in res.seeds)) == 5   # distinct seeds
    assert 0.0 < res.covered_frac <= 1.0
    assert res.influence == pytest.approx(res.covered_frac * g.n)


def test_imm_star_picks_hub():
    g = star_graph(64, p=0.9)
    res = imm(g, IMMConfig(k=1, batch=256, max_theta=2048))
    assert res.seeds[0] == 0


def test_imm_baseline_equals_efficient():
    """Paper-faithful baseline and EfficientIMM path give identical
    coverage on the same sample stream (same seed)."""
    g = rmat_graph(200, 1600, seed=7)
    r1 = imm(g, IMMConfig(k=4, batch=128, max_theta=512, seed=3,
                          selection_method="rebuild"))
    r2 = imm(g, IMMConfig(k=4, batch=128, max_theta=512, seed=3,
                          selection_method="decrement",
                          adaptive_representation=False))
    assert r1.covered_frac == pytest.approx(r2.covered_frac)
    assert r1.theta == r2.theta


def test_imm_influence_monotone_in_k():
    g = rmat_graph(200, 1600, seed=8)
    infl = [imm(g, IMMConfig(k=k, batch=128, max_theta=512)).influence
            for k in (1, 4, 8)]
    assert infl[0] <= infl[1] <= infl[2]
