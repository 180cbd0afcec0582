"""InfluenceEngine + RRRStore API: wrapper/engine equivalence, store growth
invariants, multi-query determinism, snapshot/restore, registries."""
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.engine import InfluenceEngine, IMMConfig, Selection
from repro.core.imm import imm
from repro.core.sampler import (
    default_sampler_name, get_sampler, register_sampler, registered_samplers,
)
from repro.core.selection import get_selection, register_selection
from repro.core.store import (
    BitmapStore, IndexStore, MIN_CAPACITY, make_store, next_pow2,
    store_from_state,
)
from repro.graphs import path_graph, rmat_graph
from repro.launch.mesh import make_mesh


def _random_batches(rng, n, batches, batch):
    out = []
    for _ in range(batches):
        out.append((rng.random((batch, n)) < 0.2).astype(np.uint8))
    return out


# ------------------------------------------------------------------ store ----

@pytest.mark.parametrize("kind", ["bitmap", "indices"])
def test_store_growth_preserves_counters_and_masks(kind):
    """Capacity doubling must not disturb counters, sizes, or valid rows."""
    rng = np.random.default_rng(0)
    n = 48
    store = make_store(kind, n)
    assert store.capacity == MIN_CAPACITY
    acc = np.zeros(n, np.int64)
    all_rows = []
    for batch in _random_batches(rng, n, batches=5, batch=24):
        store.add_batch(jnp.asarray(batch))
        acc += batch.sum(axis=0, dtype=np.int64)
        all_rows.append(batch)
    R_ref = np.concatenate(all_rows)
    assert store.count == 120
    assert store.capacity == next_pow2(120) == 128
    # fused counter survived every realloc
    np.testing.assert_array_equal(np.asarray(store.counter), acc)
    np.testing.assert_array_equal(
        np.asarray(store.sizes)[:120], R_ref.sum(axis=1))
    assert np.asarray(store.sizes)[120:].sum() == 0
    view = store.view()
    assert view.count == 120 and view.R.shape[0] == 128
    np.testing.assert_array_equal(
        np.asarray(view.valid), np.arange(128) < 120)
    # stored membership matches the raw batches
    if kind == "bitmap":
        np.testing.assert_array_equal(np.asarray(view.R)[:120], R_ref)
    else:
        got = np.asarray(view.R)[:120]
        for i in range(120):
            np.testing.assert_array_equal(
                np.unique(got[i][got[i] < n]), np.flatnonzero(R_ref[i]))


def test_index_store_widens_l_pad():
    n = 64
    store = IndexStore(n)
    small = np.zeros((4, n), np.uint8)
    small[:, :3] = 1
    store.add_batch(jnp.asarray(small))
    l0 = store.l_pad
    big = np.zeros((4, n), np.uint8)
    big[:, :20] = 1
    store.add_batch(jnp.asarray(big))
    assert store.l_pad == next_pow2(20, 4) > l0
    got = np.asarray(store.view().R)
    # earlier rows keep their meaning after widening (backfilled sentinel)
    np.testing.assert_array_equal(got[0][got[0] < n], np.arange(3))
    np.testing.assert_array_equal(got[4][got[4] < n], np.arange(20))


@pytest.mark.parametrize("kind", ["bitmap", "indices"])
def test_store_hits_matches_numpy(kind):
    rng = np.random.default_rng(1)
    n = 40
    store = make_store(kind, n)
    R = (rng.random((32, n)) < 0.15).astype(np.uint8)
    store.add_batch(jnp.asarray(R))
    S = np.asarray([[0, 1, 2], [5, 5, 5], [7, 30, 12]], np.int32)
    got = np.asarray(store.hits(S))
    ref = np.asarray([(R[:, s].any(axis=1)).mean() for s in S])
    np.testing.assert_allclose(got, ref, rtol=1e-6)


@pytest.mark.parametrize("kind", ["bitmap", "indices"])
def test_store_state_roundtrip(kind):
    rng = np.random.default_rng(2)
    store = make_store(kind, 32)
    store.add_batch(jnp.asarray((rng.random((20, 32)) < 0.3).astype(np.uint8)))
    clone = store_from_state(store.state())
    assert type(clone) is type(store)
    assert clone.count == store.count and clone.capacity == store.capacity
    np.testing.assert_array_equal(np.asarray(clone.R), np.asarray(store.R))
    np.testing.assert_array_equal(
        np.asarray(clone.counter), np.asarray(store.counter))


# ----------------------------------------------------------------- engine ----

@pytest.mark.parametrize("model", ["IC", "LT"])
@pytest.mark.parametrize("seed", [0, 7])
def test_imm_wrapper_reproduces_engine_seed_for_seed(model, seed):
    """The back-compat wrapper and an explicit engine must emit identical
    seeds, theta, and coverage for a fixed PRNG key."""
    g = rmat_graph(192, 1536, seed=2)
    cfg = IMMConfig(k=4, model=model, batch=128, max_theta=512, seed=seed)
    r1 = imm(g, cfg)
    r2 = InfluenceEngine(g, cfg).run()
    np.testing.assert_array_equal(r1.seeds, r2.seeds)
    assert r1.theta == r2.theta
    assert r1.covered_frac == pytest.approx(r2.covered_frac)
    np.testing.assert_array_equal(r1.counter, r2.counter)


def test_engine_multi_query_without_resampling():
    """>= 2 successive select(k) calls answer from one sampled store."""
    g = rmat_graph(256, 2048, seed=1)
    engine = InfluenceEngine(g, IMMConfig(k=8, batch=128, max_theta=1024))
    engine.run()
    theta = engine.theta
    a = engine.select(5)
    b = engine.select(5)
    c = engine.select(8)
    assert engine.theta == theta                  # no re-sampling happened
    assert a is b                                 # memoized
    np.testing.assert_array_equal(a.seeds, b.seeds)
    np.testing.assert_array_equal(a.seeds, c.seeds[:5])
    assert a.influence <= c.influence + 1e-6


def test_engine_extend_is_idempotent_and_monotone():
    g = rmat_graph(128, 1024, seed=3)
    engine = InfluenceEngine(g, IMMConfig(batch=64))
    assert engine.extend(100) >= 100
    got = engine.theta
    assert engine.extend(50) == got               # already satisfied
    assert engine.extend(got + 1) >= got + 1


def test_engine_influence_consistent_with_selection():
    g = rmat_graph(256, 2048, seed=1)
    engine = InfluenceEngine(g, IMMConfig(k=5, batch=128, max_theta=512))
    engine.extend(512)
    sel = engine.select(5)
    assert engine.influence(sel.seeds) == pytest.approx(sel.influence, rel=1e-6)
    vals = engine.influences([sel.seeds[:1], sel.seeds[:3], sel.seeds])
    assert vals[0] <= vals[1] <= vals[2] + 1e-9   # monotone in |S|
    with pytest.raises(ValueError):
        engine.influence([])
    with pytest.raises(ValueError):
        engine.influence([g.n + 5])


def test_engine_snapshot_restore_roundtrip():
    g = rmat_graph(200, 1600, seed=5)
    cfg = IMMConfig(k=4, batch=64, max_theta=512, seed=9)
    engine = InfluenceEngine(g, cfg)
    engine.run()
    want = engine.select(4)
    with tempfile.TemporaryDirectory() as d:
        assert engine.snapshot(d) is not None
        fresh = InfluenceEngine(g, cfg)
        assert fresh.restore(d)
        assert fresh.theta == engine.theta
        got = fresh.select(4)
        np.testing.assert_array_equal(got.seeds, want.seeds)
        # restored engines keep sampling from the snapshotted key stream
        fresh.extend(fresh.theta + 64)
        assert fresh.theta == engine.theta + 64
        # restore into a mismatched problem is refused
        other = InfluenceEngine(rmat_graph(64, 256, seed=0), cfg)
        with pytest.raises(ValueError):
            other.restore(d)


def test_engine_restore_returns_false_when_empty():
    g = rmat_graph(64, 256, seed=0)
    with tempfile.TemporaryDirectory() as d:
        assert not InfluenceEngine(g, IMMConfig()).restore(d)


def test_engine_index_store_backend_end_to_end():
    """The sparse-native arena answers the same API (seeds may differ from
    the dense backend only via float argmax ties)."""
    g = path_graph(512, p=0.5)
    engine = InfluenceEngine(
        g, IMMConfig(k=4, batch=64, max_theta=256, store="indices"))
    res = engine.run()
    assert res.representation == "indices"
    assert len(set(res.seeds.tolist())) == 4
    assert engine.influence(res.seeds) == pytest.approx(res.influence, rel=1e-6)


def test_native_index_emission_matches_bitmap_and_caps_width():
    """IndexStore + sparse backend emits lists natively (C4 routed
    per-backend): same seed -> identical counters/selections as the
    bitmap arena, with the emission width capped at exactly n (not the
    next power of two — top_k cannot exceed the bitmap minor dim) even
    when dense reachability fills every row on a non-pow2 n."""
    g = rmat_graph(100, 3000, seed=0)          # dense sets, n not pow2
    kw = dict(k=4, batch=16, max_theta=128, seed=1, backend="sparse")
    ei = InfluenceEngine(g, IMMConfig(store="indices", **kw))
    eb = InfluenceEngine(g, IMMConfig(store="bitmap", **kw))
    assert ei._emit_l > 0                      # native emission engaged
    ei.extend(64)
    eb.extend(64)
    assert ei._emit_l <= g.n
    np.testing.assert_array_equal(np.asarray(ei.store.counter),
                                  np.asarray(eb.store.counter))
    np.testing.assert_array_equal(ei.select(4).seeds, eb.select(4).seeds)


def test_restore_across_store_kinds_resets_index_emission():
    """Snapshots are elastic across store kinds: an indices-configured
    engine restoring a bitmap snapshot must drop native index emission,
    or its next extend would call add_index_batch on a BitmapStore."""
    g = rmat_graph(100, 3000, seed=0)
    kw = dict(k=4, batch=16, max_theta=128, seed=1, backend="sparse")
    src = InfluenceEngine(g, IMMConfig(store="bitmap", **kw))
    src.extend(32)
    with tempfile.TemporaryDirectory() as d:
        src.snapshot(d)
        idx = InfluenceEngine(g, IMMConfig(store="indices", **kw))
        assert idx._emit_l > 0
        assert idx.restore(d)
        assert isinstance(idx.store, BitmapStore) and idx._emit_l == 0
        idx.extend(64)                         # bitmap write path, no crash
        src.extend(64)
        np.testing.assert_array_equal(np.asarray(idx.store.counter),
                                      np.asarray(src.store.counter))


# ------------------------------------------------------------- registries ----

def test_sampler_registry_resolves_and_rejects():
    g = rmat_graph(64, 256, seed=0)
    assert default_sampler_name(g, IMMConfig(model="IC")) == "IC/dense"
    assert default_sampler_name(
        g, IMMConfig(model="IC", dense_sampler_max_n=8)) == "IC/sparse"
    assert default_sampler_name(g, IMMConfig(model="LT")) == "LT/walk"
    assert default_sampler_name(
        g, IMMConfig(model="WC", stable=True)) == "WC/dense+stable"
    assert default_sampler_name(
        g, IMMConfig(model="GT", backend="pallas")) == "GT/pallas"
    # canonical matrix names and deprecated legacy aliases all resolve
    assert {"IC/dense", "WC/sparse", "GT/pallas+stable", "LT/walk",
            "IC-dense", "IC-sparse", "LT"} <= set(registered_samplers())
    with pytest.raises(ValueError):
        get_sampler("no-such-sampler")
    with pytest.raises(ValueError):
        default_sampler_name(g, IMMConfig(model="SIR"))


def test_custom_sampler_plugs_into_engine():
    g = rmat_graph(64, 256, seed=0)

    @register_sampler("test-root-only")
    def _factory(graph, cfg):
        def sample(key):
            roots = jax.random.randint(key, (cfg.batch,), 0, graph.n)
            visited = jax.nn.one_hot(roots, graph.n, dtype=jnp.uint8)
            return visited, visited.sum(0).astype(jnp.int32), roots
        return sample

    engine = InfluenceEngine(
        g, IMMConfig(k=2, batch=32, max_theta=64, sampler="test-root-only"))
    engine.extend(64)
    sel = engine.select(2)
    assert engine.theta == 64 and len(sel.seeds) == 2


def test_selection_registry_covers_matrix_and_rejects():
    for method in ("rebuild", "decrement"):
        for layout in ("dense", "sparse", "packed", "compressed", "sharded",
                       "sharded-sparse"):
            assert callable(get_selection(method, layout))
    with pytest.raises(ValueError):
        get_selection("rebuild", "no-such-layout")


def test_sharded_strategy_through_engine_matches_local():
    """Sharded selection via the strategy interface == local selection."""
    mesh = make_mesh((1, 1), ("data", "model"))
    g = rmat_graph(128, 1024, seed=4)
    cfg = IMMConfig(k=5, batch=64, max_theta=256)
    local = InfluenceEngine(g, cfg)
    sharded = InfluenceEngine(g, cfg, mesh=mesh, theta_axes=("data",))
    local.extend(256)
    sharded.extend(256)
    a = local.select(5)
    b = sharded.select(5)
    np.testing.assert_array_equal(a.seeds, b.seeds)
    assert a.covered_frac == pytest.approx(b.covered_frac)
