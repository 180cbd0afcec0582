"""The sparse IC step as a pull over source-sorted edges.

Its coins are drawn by counter in the edge-major layout and must equal
``uniform(s, (B, m))`` transposed, bit for bit; the loop must give the
rows, roots, counter and trip count of the scatter step it replaced,
kept here as an oracle only.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import sampler as smp
from repro.core.adaptive import bitmap_to_indices
from repro.core.engine import IMMConfig, InfluenceEngine
from repro.core.sampler import bind_sampler, make_sampler
from repro.graphs import rmat_graph


def graph():
    return rmat_graph(160, 1400, seed=5)


# ------------------------------------------------------------- coins ---

@pytest.mark.parametrize("batch,m,seed", [(1, 1, 0), (4, 7, 1), (32, 768, 5),
                                          (256, 1000, 123), (3, 4097, 2**31)])
def test_counter_coins_are_uniform_transposed(batch, m, seed):
    s = jax.random.split(jax.random.PRNGKey(seed))[1]
    want = np.asarray(jax.random.uniform(s, (batch, m))).T
    words = smp._counter_words(*smp._counter_base(batch, m),
                               jnp.arange(m, dtype=jnp.int32))
    got = np.asarray(jax.jit(smp._uniform_at)(s, *words))
    assert got.shape == (m, batch)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_counter_coins_at_a_permuted_position():
    """Coins drawn at a permutation of positions are that permutation of
    the CSC-layout coins."""
    batch, m = 8, 300
    s = jax.random.PRNGKey(9)
    pos = np.random.default_rng(0).permutation(m).astype(np.int32)
    want = np.asarray(jax.random.uniform(s, (batch, m)))[:, pos].T
    got = smp._uniform_at(s, *smp._counter_words(
        *smp._counter_base(batch, m), jnp.asarray(pos)))
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  want.view(np.uint32))


def test_counter_words_split_exactly_past_2_32():
    batch, m = 256, 20_000_003               # batch * m > 5 * 2**32
    pos = np.array([0, 1, 77, 12_345_678, m - 2, m - 1], np.int32)
    hi, lo = smp._counter_words(*smp._counter_base(batch, m),
                                jnp.asarray(pos))
    got = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) \
        | np.asarray(lo).astype(np.uint64)
    want = (np.arange(batch, dtype=np.uint64)[None, :] * np.uint64(m)
            + pos.astype(np.uint64)[:, None])
    assert want.max() > 2**32
    np.testing.assert_array_equal(got, want)


# ---------------------------------------- the step it replaced (oracle) ---

def scatter_rows(key, src, dst, prob, positions=None, *, n, batch, stable):
    """The sparse loop before the pull: (K, n) state, a lane gather of
    ``frontier[:, dst]`` and a scatter-or into the sources each step."""
    kstep, roots, visited, bb = smp._setup(key, batch, n, positions, None,
                                           stable)
    uid = (src.astype(jnp.uint32) * jnp.uint32(n)
           + dst.astype(jnp.uint32))[None, :]
    frontier, steps = visited, 0
    while bool(frontier.any()):
        kstep, sub = jax.random.split(kstep)
        if stable:
            kd = jnp.asarray(sub, jnp.uint32).reshape(-1)
            coin = smp._u01(smp._mix32(smp._mix32(uid ^ kd[0]) ^ bb ^ kd[1]))
        else:
            coin = jax.random.uniform(sub, (batch, src.shape[0]))
        live = frontier[:, dst] & (coin < prob[None, :]) & ~visited[:, src]
        frontier = jnp.zeros_like(visited).at[:, src].max(live) & ~visited
        visited, steps = visited | frontier, steps + 1
    return np.asarray(visited, np.uint8), np.asarray(roots), steps


def oracle(model, stable, key, positions=None, batch=32):
    g = graph()
    prob = jnp.asarray(smp.get_model(model).edge_probs(g), jnp.float32)
    return scatter_rows(key, jnp.asarray(g.edge_src), jnp.asarray(g.edge_dst),
                        prob, positions, n=g.n, batch=batch, stable=stable)


def bound(model, stable, batch=32):
    return bind_sampler(make_sampler(model, "sparse", stable=stable),
                        graph(), IMMConfig(batch=batch))


CELLS = [(m, s) for m in ("IC", "WC") for s in (False, True)]


@pytest.mark.parametrize("model,stable", CELLS)
def test_pull_rows_equal_the_scatter_step(model, stable):
    key = jax.random.PRNGKey(21)
    rows, roots, steps = oracle(model, stable, key)
    v, c, r, s = bound(model, stable)(key, with_steps=True)
    assert 0 < rows.sum() and (rows.sum(axis=1) > 1).any()
    np.testing.assert_array_equal(np.asarray(v), rows)
    np.testing.assert_array_equal(np.asarray(c), rows.sum(axis=0))
    np.testing.assert_array_equal(np.asarray(r), roots)
    assert int(s) == steps


@pytest.mark.parametrize("model", ["IC", "WC"])
def test_pull_positions_equal_the_scatter_step(model):
    key = jax.random.PRNGKey(4)
    pos = jnp.asarray([5, 31, 17, 4, 0], jnp.int32)
    rows, roots, _ = oracle(model, True, key, positions=pos)
    v, c, r = bound(model, True)(key, positions=pos)
    np.testing.assert_array_equal(np.asarray(v), rows)
    np.testing.assert_array_equal(np.asarray(c), rows.sum(axis=0))
    np.testing.assert_array_equal(np.asarray(r), roots)


@pytest.mark.parametrize("model,stable", CELLS)
def test_pull_index_rows_equal_the_scatter_step(model, stable):
    key = jax.random.PRNGKey(8)
    rows, _, steps = oracle(model, stable, key)
    width = int(rows.sum(axis=1).max())
    idx, c, _, s = bound(model, stable)(key, emit_l=width, with_steps=True)
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(bitmap_to_indices(jnp.asarray(rows),
                                                      width)))
    np.testing.assert_array_equal(np.asarray(c), rows.sum(axis=0))
    assert int(s) == steps


def test_sample_span_consulted_is_the_scatter_steps():
    """The fused chain's ``consulted`` (row, edge) pairs read the same
    from the pull's rows as from the scatter step's."""
    g = graph()
    obs.reset()
    obs.enable()
    try:
        eng = InfluenceEngine(g, IMMConfig(batch=32, seed=6, store="bitmap",
                                           dense_sampler_max_n=8))
        assert eng.sampler_name == "IC/sparse"
        eng.extend(32)
        spans = [e for e in obs.chrome_trace()["traceEvents"]
                 if e["ph"] == "X" and e["name"] == "sample"]
    finally:
        obs.reset()
    args = spans[0]["args"]
    sub = jax.random.split(jax.random.PRNGKey(6))[1]
    rows, _, steps = oracle("IC", False, sub)
    in_deg = np.bincount(np.asarray(g.edge_dst), minlength=g.n)
    assert args["consulted"] == int((rows * in_deg).sum()) > 0
    assert args["steps"] == steps


@pytest.mark.parametrize("stable", [False, True])
def test_pull_through_the_kernel_equals_the_scatter_step(stable):
    """``pallas_interpret`` runs the loop's reduction through the
    segment_or kernel itself (interpreted): the same rows."""
    key = jax.random.PRNGKey(13)
    rows, roots, steps = oracle("IC", stable, key)
    fn = bind_sampler(make_sampler("IC", "sparse", stable=stable), graph(),
                      IMMConfig(batch=32, pallas_interpret=True))
    v, _, r, s = fn(key, with_steps=True)
    np.testing.assert_array_equal(np.asarray(v), rows)
    np.testing.assert_array_equal(np.asarray(r), roots)
    assert int(s) == steps
