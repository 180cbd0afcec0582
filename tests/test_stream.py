"""Streaming subsystem: GraphDelta semantics, reverse-touch invalidation,
StreamEngine refresh equivalence (the headline invariant), bounded-memory
eviction/compaction, snapshot provenance, and IMServer epoch-consistent
serving.

Mesh-touching tests use however many devices the process has — 1 in a
plain run, 4 under scripts/ci.sh's forced-4-device pass, where the
per-shard eviction/compaction paths run with real multi-device buffers.
"""
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.imm_snap import make_im_mesh, mesh_engine_kwargs
from repro.core.engine import InfluenceEngine, IMMConfig
from repro.core.store import (
    BitmapStore, IndexStore, ShardedStore, StorePressurePolicy, make_store,
    store_from_state,
)
from repro.graphs import rmat_graph
from repro.graphs.csr import build_graph, dense_ic_matrix, edge_arrays
from repro.launch.serve import IMServer
from repro.stream import (
    GraphDelta, StreamEngine, canonicalize, invalidate, random_delta,
    rows_touching,
)
from repro.launch.mesh import make_mesh


def theta_mesh():
    return make_mesh((jax.device_count(),), ("data",))


def small_graph(seed=2):
    return rmat_graph(96, 768, seed=seed)


# ------------------------------------------------------------- GraphDelta ----

def test_delta_apply_matches_dense_matrix():
    """CSR rebuild and dense-matrix scatter agree edge-for-edge."""
    g = canonicalize(small_graph())
    rng = np.random.default_rng(0)
    d = random_delta(g, rng, inserts=5, deletes=4, reweights=3)
    g2 = d.apply(g)
    P2 = d.apply_dense(dense_ic_matrix(g))
    np.testing.assert_allclose(np.asarray(dense_ic_matrix(g2)),
                               np.asarray(P2), rtol=1e-6)
    assert g2.m == g.m + 5 - 4


def test_delta_untouched_edges_are_bit_identical():
    """Edges whose dst was not mutated keep exact probs and LT weights."""
    g = canonicalize(small_graph())
    rng = np.random.default_rng(1)
    d = random_delta(g, rng, inserts=2, deletes=2, reweights=2)
    g2 = d.apply(g)
    touched = set(d.touched_vertices().tolist())
    s1, d1, p1, w1 = edge_arrays(g)
    s2, d2, p2, w2 = edge_arrays(g2)
    e1 = {(int(u), int(v)): (p, w) for u, v, p, w in zip(s1, d1, p1, w1)}
    e2 = {(int(u), int(v)): (p, w) for u, v, p, w in zip(s2, d2, p2, w2)}
    for (u, v), (p, w) in e1.items():
        if v in touched or (u, v) not in e2:
            continue
        assert e2[(u, v)] == (p, w)
    # untouched dst segments keep bit-identical LT cum arrays and totals
    lt1 = np.asarray(g.in_lt_total)
    lt2 = np.asarray(g2.in_lt_total)
    for v in range(g.n):
        if v not in touched:
            assert lt1[v] == lt2[v]


def test_delta_strict_validation():
    g = canonicalize(small_graph())
    src = np.asarray(g.in_src)
    dst = np.asarray(g.edge_dst)
    with pytest.raises(ValueError, match="insert of existing"):
        GraphDelta.inserts([src[0]], [dst[0]], [0.5]).apply(g)
    absent_u, absent_v = 0, 1
    existing = set(zip(src.tolist(), dst.tolist()))
    while (absent_u, absent_v) in existing or absent_u == absent_v:
        absent_v += 1
    with pytest.raises(ValueError, match="delete of missing"):
        GraphDelta.deletes([absent_u], [absent_v]).apply(g)
    with pytest.raises(ValueError, match="reweight of missing"):
        GraphDelta.reweights([absent_u], [absent_v], [0.3]).apply(g)
    with pytest.raises(ValueError, match="out of range"):
        GraphDelta.inserts([0], [g.n + 3], [0.5]).apply(g)
    with pytest.raises(ValueError, match="probabilities"):
        GraphDelta.inserts([absent_u], [absent_v], [-0.5])
    with pytest.raises(ValueError, match="probabilities"):
        GraphDelta.reweights([src[0]], [dst[0]], [1.5])
    # insert-then-delete inside one delta cancels out
    d = GraphDelta.concat([
        GraphDelta.inserts([absent_u], [absent_v], [0.5]),
        GraphDelta.deletes([absent_u], [absent_v]),
    ])
    assert d.apply(g).m == g.m


def test_delta_lt_totals_stay_bounded():
    """Inserted LT weights keep every per-dst total < 1."""
    g = canonicalize(small_graph())
    rng = np.random.default_rng(3)
    for _ in range(3):
        g = random_delta(g, rng, inserts=8, reweights=4).apply(g)
    assert float(np.asarray(g.in_lt_total).max()) < 1.0


def test_canonicalize_is_idempotent():
    g = canonicalize(small_graph())
    g2 = canonicalize(g)
    for field in ("in_prob", "in_lt_cum", "in_lt_total", "in_src",
                  "edge_dst"):
        np.testing.assert_array_equal(np.asarray(getattr(g, field)),
                                      np.asarray(getattr(g2, field)))


@pytest.mark.parametrize("name", ["IC-dense-stable", "IC-sparse-stable",
                                  "LT-stable"])
def test_stable_samplers_regenerate_row_subsets_exactly(name):
    """positions=(...) re-generates exactly those rows of the batch —
    the hook that makes refresh work scale with stale rows."""
    from repro.core.sampler import bind_sampler, get_sampler
    g = canonicalize(small_graph())
    model = "LT" if name == "LT-stable" else "IC"
    cfg = IMMConfig(batch=32, model=model, sampler=name)
    fn = bind_sampler(get_sampler(name), g, cfg)
    key = jax.random.PRNGKey(5)
    full, _, roots = fn(key)
    pos = np.asarray([3, 17, 4, 31])
    sub, _, sub_roots = fn(key, positions=jnp.asarray(pos, jnp.int32))
    np.testing.assert_array_equal(np.asarray(sub), np.asarray(full)[pos])
    np.testing.assert_array_equal(np.asarray(sub_roots),
                                  np.asarray(roots)[pos])


# ----------------------------------------------------------- invalidation ----

@pytest.mark.parametrize("kind", ["bitmap", "indices"])
def test_rows_touching_matches_numpy(kind):
    rng = np.random.default_rng(4)
    n = 40
    store = make_store(kind, n)
    R = (rng.random((24, n)) < 0.2).astype(np.uint8)
    store.add_batch(jnp.asarray(R))
    verts = np.asarray([3, 17, 31])
    got = np.asarray(rows_touching(store, verts))[:24]
    np.testing.assert_array_equal(got, R[:, verts].any(axis=1))


def test_invalidate_drops_rows_from_serving_immediately():
    """Stale rows leave select/hits/counter with no rebuild."""
    rng = np.random.default_rng(5)
    n = 48
    store = BitmapStore(n)
    R = (rng.random((64, n)) < 0.25).astype(np.uint8)
    store.add_batch(jnp.asarray(R))
    verts = np.asarray([7, 11])
    stale = R[:, verts].any(axis=1)
    assert invalidate(store, verts) == int(stale.sum()) > 0
    assert store.live_count == 64 - int(stale.sum())
    np.testing.assert_array_equal(np.asarray(store.counter),
                                  R[~stale].sum(axis=0))
    # hits normalize over surviving rows only
    S = np.asarray([[0, 1]], np.int32)
    want = R[~stale][:, [0, 1]].any(axis=1).mean()
    assert float(store.hits(S)[0]) == pytest.approx(want)
    # view().valid excludes them, so any selection strategy skips them
    v = store.view()
    np.testing.assert_array_equal(np.asarray(v.valid)[:64], ~stale)


def test_invalidate_sharded_matches_single_device():
    rng = np.random.default_rng(6)
    n = 36
    bs, ss = BitmapStore(n), ShardedStore(n, mesh=theta_mesh())
    R = (rng.random((40, n)) < 0.25).astype(np.uint8)
    bs.add_batch(jnp.asarray(R))
    ss.add_batch(jnp.asarray(R))
    verts = np.asarray([1, 2, 3])
    assert invalidate(bs, verts) == invalidate(ss, verts)
    np.testing.assert_array_equal(np.asarray(bs.counter),
                                  np.asarray(ss.counter))
    assert bs.live_count == ss.live_count


# --------------------------------------------------- eviction / compaction ----

def test_pressure_policy_row_caps():
    assert StorePressurePolicy(max_rows=100).row_cap(64) == 100
    assert StorePressurePolicy(max_bytes=6400).row_cap(64) == 100
    assert StorePressurePolicy(max_rows=50, max_bytes=6400).row_cap(64) == 50
    assert StorePressurePolicy().row_cap(64) is None
    with pytest.raises(ValueError):
        StorePressurePolicy(max_bytes=10).row_cap(64)


def test_compact_preserves_live_rows_and_remaps():
    rng = np.random.default_rng(7)
    n = 32
    store = BitmapStore(n)
    store.track_remaps = True
    R = (rng.random((48, n)) < 0.3).astype(np.uint8)
    store.add_batch(jnp.asarray(R))
    dead = np.zeros(store.capacity, bool)
    dead[[3, 10, 40]] = True
    store.kill_rows(dead)
    remap = store.compact()
    assert store.count == 45 and store.dead == 0
    keep = ~dead[:48]
    np.testing.assert_array_equal(np.asarray(store.R)[:45], R[keep])
    # remap follows every surviving row to its new slot
    for old in np.flatnonzero(keep):
        np.testing.assert_array_equal(
            np.asarray(store.R)[remap[old]], R[old])
    assert all(remap[i] == -1 for i in (3, 10, 40))
    assert len(store.drain_remaps()) == 1 and not store.drain_remaps()


def test_eviction_is_staleness_first_then_fifo():
    """Under pressure, dead rows are reclaimed before any live row, and
    live victims go oldest-first."""
    rng = np.random.default_rng(8)
    n = 24
    store = BitmapStore(n, policy=StorePressurePolicy(max_rows=32))
    R = (rng.random((32, n)) < 0.4).astype(np.uint8)
    store.add_batch(jnp.asarray(R))
    dead = np.zeros(store.capacity, bool)
    dead[:8] = True
    store.kill_rows(dead)
    newer = (rng.random((8, n)) < 0.4).astype(np.uint8)
    store.add_batch(jnp.asarray(newer))       # fits exactly in freed slots
    assert store.capacity == 32 and store.count == 32
    np.testing.assert_array_equal(np.asarray(store.R)[:24], R[8:])
    np.testing.assert_array_equal(np.asarray(store.R)[24:], newer)
    # now no dead rows: the next batch evicts the *oldest* live rows
    extra = (rng.random((4, n)) < 0.4).astype(np.uint8)
    store.add_batch(jnp.asarray(extra))
    got = np.asarray(store.R)
    np.testing.assert_array_equal(got[:20], R[12:])
    np.testing.assert_array_equal(got[28:], extra)
    assert store.count == 32


def test_sharded_store_respects_cap_per_shard():
    """Per-shard buffer shapes never exceed the policy's per-shard share
    across repeated writes (the bounded-memory acceptance shape check)."""
    n = 24
    mesh = theta_mesh()
    store = ShardedStore(n, mesh=mesh, policy=StorePressurePolicy(max_rows=64))
    rng = np.random.default_rng(9)
    local_cap = 64 // store.D
    for _ in range(8):
        store.add_batch(jnp.asarray(
            (rng.random((16, n)) < 0.3).astype(np.uint8)))
        assert store.capacity <= 64
        assert store.cap_local <= local_cap
        # every per-device buffer is exactly (cap_local, n) — the cap
        # holds physically, shard by shard, not just as bookkeeping
        assert all(s.data.shape == (store.cap_local, n)
                   for s in store.R.addressable_shards)
    assert store.count <= 64 and store.live_count <= 64


def test_stream_extend_terminates_on_non_divisible_cap():
    """A cap that is not a multiple of the shard count must clamp to the
    attainable D*(cap//D) rows instead of hanging extend-to-cap loops."""
    g = small_graph()
    cfg = IMMConfig(k=3, batch=16, seed=0)
    stream = StreamEngine(g, cfg, mesh=theta_mesh(),
                          policy=StorePressurePolicy(max_rows=70))
    D = stream.store.D
    attainable = (70 // D) * D
    assert stream.store.row_cap == attainable
    assert stream.extend(100) == attainable
    assert stream.refresh() == 0


def test_index_store_lifecycle_roundtrip():
    """kill/replace/compact work on the index-list arena too."""
    rng = np.random.default_rng(10)
    n = 40
    store = IndexStore(n)
    R = (rng.random((16, n)) < 0.2).astype(np.uint8)
    store.add_batch(jnp.asarray(R))
    dead = np.zeros(store.capacity, bool)
    dead[[2, 5]] = True
    store.kill_rows(dead)
    np.testing.assert_array_equal(
        np.asarray(store.counter),
        np.delete(R, [2, 5], axis=0).sum(axis=0))
    repl = (rng.random((2, n)) < 0.5).astype(np.uint8)
    store.replace_rows(np.asarray([2, 5]), jnp.asarray(repl))
    want = R.copy()
    want[[2, 5]] = repl
    np.testing.assert_array_equal(np.asarray(store.counter), want.sum(0))
    assert store.live_count == 16


def test_snapshot_drops_stale_rows():
    """state()/restore round-trips live rows only, on both layouts."""
    rng = np.random.default_rng(11)
    n = 28
    R = (rng.random((20, n)) < 0.3).astype(np.uint8)
    for store in (BitmapStore(n), ShardedStore(n, mesh=theta_mesh())):
        slots = store.add_batch(jnp.asarray(R))
        dead = np.zeros(store.capacity, bool)
        dead[slots[[0, 7]]] = True            # batch rows 0 and 7
        store.kill_rows(dead)
        clone = store_from_state(store.state())
        assert clone.live_count == 18
        np.testing.assert_array_equal(np.asarray(clone.counter),
                                      np.asarray(store.counter))


# ------------------------------------------------- the headline invariant ----

def _assert_stream_equals_fresh(stream, cfg, k=5):
    # stream.cfg carries the delta-stable sampler upgrade; the fresh
    # reference must sample with the same registry entry
    fresh = InfluenceEngine(stream.graph, stream.cfg)
    fresh.extend(stream.theta)
    a, b = stream.select(k), fresh.select(k)
    np.testing.assert_array_equal(a.seeds, b.seeds)
    assert a.covered_frac == pytest.approx(b.covered_frac)
    np.testing.assert_array_equal(np.asarray(stream.store.counter),
                                  np.asarray(fresh.store.counter))
    np.testing.assert_allclose(
        stream.influences([a.seeds[:2], a.seeds]),
        fresh.influences([a.seeds[:2], a.seeds]), rtol=1e-6)


@pytest.mark.parametrize("sampler", ["IC-dense", "IC-sparse"])
def test_refresh_equivalence_single_device(sampler):
    """After any delta sequence, refreshing until stale == 0 serves
    exactly what a fresh engine on the post-delta graph would."""
    cfg = IMMConfig(k=5, batch=64, max_theta=512, seed=7, sampler=sampler)
    stream = StreamEngine(small_graph(), cfg)
    assert stream.cfg.sampler == f"{sampler}-stable"
    assert stream.engine.supports_row_resample
    stream.extend(256)
    rng = np.random.default_rng(12)
    for _ in range(3):                        # deltas without refresh between
        stream.apply_delta(random_delta(
            stream.graph, rng, inserts=3, deletes=3, reweights=2))
    assert stream.refresh() == 0 and stream.consistent
    _assert_stream_equals_fresh(stream, cfg)


@pytest.mark.parametrize("sampler", ["IC-dense", "IC-sparse"])
def test_refresh_equivalence_mesh(sampler):
    """Same invariant with the stream's store mesh-sharded; the fresh
    reference runs single-device (layout independence both ways)."""
    cfg = IMMConfig(k=5, batch=64, max_theta=512, seed=3, sampler=sampler)
    stream = StreamEngine(small_graph(), cfg, mesh=theta_mesh())
    assert isinstance(stream.store, ShardedStore)
    stream.extend(192)
    rng = np.random.default_rng(13)
    for _ in range(2):
        stream.apply_delta(random_delta(
            stream.graph, rng, inserts=2, deletes=2, reweights=2))
        stream.refresh()                      # refresh between deltas too
    assert stream.stale == 0
    _assert_stream_equals_fresh(stream, cfg)


@pytest.mark.slow
def test_refresh_equivalence_lt_model():
    """The LT walk re-samples stably through canonicalized rebuilds."""
    cfg = IMMConfig(k=4, batch=64, max_theta=512, seed=5, model="LT")
    stream = StreamEngine(small_graph(), cfg)
    stream.extend(256)
    rng = np.random.default_rng(14)
    for _ in range(3):
        stream.apply_delta(random_delta(
            stream.graph, rng, inserts=3, deletes=3, reweights=3))
    assert stream.refresh() == 0
    _assert_stream_equals_fresh(stream, cfg, k=4)


@pytest.mark.slow
def test_budgeted_refresh_converges_incrementally():
    """Row-budgeted refresh makes monotone progress and lands on the
    same fixed point as one unbudgeted refresh."""
    cfg = IMMConfig(k=4, batch=32, max_theta=512, seed=9)
    stream = StreamEngine(small_graph(), cfg)
    stream.extend(256)
    rng = np.random.default_rng(15)
    stream.apply_delta(random_delta(
        stream.graph, rng, inserts=4, deletes=4, reweights=4))
    backlog = stream.stale
    assert backlog > 0
    steps = 0
    while stream.stale:
        left = stream.refresh(budget=48)
        assert left <= backlog
        backlog = left
        steps += 1
        assert steps < 64
    _assert_stream_equals_fresh(stream, cfg, k=4)


def test_epoch_tags_and_memoization_invalidate_on_delta():
    cfg = IMMConfig(k=3, batch=32, max_theta=256, seed=1)
    stream = StreamEngine(small_graph(), cfg)
    stream.extend(128)
    a = stream.select(3)
    assert a.epoch == 0 and a.stale == 0
    rng = np.random.default_rng(16)
    stream.apply_delta(random_delta(stream.graph, rng, deletes=6))
    b = stream.select(3)
    assert b.epoch == 1 and b.stale > 0      # answered from survivors
    assert stream.theta < 128
    # memoization did not serve the pre-delta answer: the new epoch's
    # selection was recomputed against fewer (surviving) rows
    assert b.theta == stream.theta < a.theta
    stream.refresh()
    c = stream.select(3)
    assert c.epoch == 1 and c.stale == 0 and c.theta == 128
    # the repaired store answers sigma for the *current* graph — pin that
    # it can't echo the pre-delta memo entry by comparing against a fresh
    # engine on the post-delta graph
    fresh = InfluenceEngine(stream.graph, stream.cfg)
    fresh.extend(128)
    assert stream.influence(c.seeds) == pytest.approx(
        fresh.influence(c.seeds), rel=1e-6)


def test_bounded_stream_keeps_cap_and_quality():
    """10-delta stream under max_rows: capacity never exceeds the cap
    while selection quality stays within 2% of the unbounded store."""
    g = small_graph()
    cfg = IMMConfig(k=5, batch=64, max_theta=4096, seed=4)
    cap = 512
    bounded = StreamEngine(g, cfg, policy=StorePressurePolicy(max_rows=cap))
    unbounded = StreamEngine(g, cfg)
    bounded.extend(1024)                      # clamps to the cap
    unbounded.extend(1024)
    assert bounded.theta == cap
    rng_b, rng_u = (np.random.default_rng(17) for _ in range(2))
    for _ in range(10):
        d = random_delta(bounded.graph, rng_b, inserts=2, deletes=2,
                         reweights=2, max_dst_indeg=6)
        bounded.apply_delta(d)
        bounded.refresh()
        assert bounded.store.capacity <= cap
        assert np.asarray(bounded.store.R).shape[0] <= cap
        d2 = random_delta(unbounded.graph, rng_u, inserts=2, deletes=2,
                          reweights=2, max_dst_indeg=6)
        unbounded.apply_delta(d2)
        unbounded.refresh()
    # identical delta streams (same rng seed) => same final graph
    np.testing.assert_array_equal(np.asarray(bounded.graph.in_src),
                                  np.asarray(unbounded.graph.in_src))
    sb = bounded.select(5)
    su = unbounded.select(5)
    # judge both seed sets on the unbounded (higher-theta) estimator
    sigma_b, sigma_u = unbounded.influences([sb.seeds, su.seeds])
    assert sigma_b >= 0.98 * sigma_u


# ---------------------------------------------- snapshot provenance ----

def _layout_kwargs(side):
    """Engine keyword arguments for a snapshot-layout side: single
    device, a 1D theta mesh, or a 2D theta x vertex mesh."""
    if side == "flat":
        return {}
    if side == "mesh":
        return {"mesh": theta_mesh()}
    d = jax.device_count()
    return mesh_engine_kwargs(
        make_im_mesh((d // 2, 2) if d % 2 == 0 else (d, 1)))


@pytest.mark.parametrize("layouts", ["flat->flat", "mesh->mesh",
                                     "flat->mesh", "mesh->flat",
                                     "flat->2d", "2d->flat",
                                     "mesh->2d", "2d->2d"])
def test_stream_snapshot_restores_batch_key_provenance(layouts):
    """A restored stream same-key repairs instead of topping up: after
    snapshot/restore (across any store-layout pair, including onto and
    off a 2D theta x vertex mesh), a delta + refresh leaves the store
    seed-for-seed equal to the original stream's — and to a fresh engine
    on the post-delta graph."""
    src_kw, dst_kw = [_layout_kwargs(side)
                      for side in layouts.split("->")]
    g = small_graph()
    cfg = IMMConfig(k=4, batch=64, max_theta=512, seed=7)
    original = StreamEngine(g, cfg, **src_kw)
    original.extend(256)
    with tempfile.TemporaryDirectory() as d:
        original.snapshot(d)
        restored = StreamEngine(g, cfg, **dst_kw)
        assert restored.restore(d)
    assert restored.theta == 256 and restored.target_theta == 256
    filled = np.flatnonzero(restored._slot_batch >= 0)
    assert filled.size == 256          # every live row kept its provenance
    rng_a, rng_b = (np.random.default_rng(22) for _ in range(2))
    original.apply_delta(random_delta(original.graph, rng_a, inserts=3,
                                      deletes=3, reweights=2))
    restored.apply_delta(random_delta(restored.graph, rng_b, inserts=3,
                                      deletes=3, reweights=2))
    assert original.refresh() == 0 and restored.refresh() == 0
    a, b = original.select(4), restored.select(4)
    np.testing.assert_array_equal(a.seeds, b.seeds)
    np.testing.assert_array_equal(np.asarray(original.store.counter),
                                  np.asarray(restored.store.counter))
    _assert_stream_equals_fresh(restored, cfg, k=4)


def test_stream_snapshot_keeps_dead_row_provenance_single_device():
    """A single-device snapshot taken mid-repair (stale rows resident)
    restores the dead rows' provenance too, so the restored stream
    finishes the same-key repair the saved one had pending."""
    g = small_graph()
    cfg = IMMConfig(k=4, batch=64, max_theta=512, seed=9)
    stream = StreamEngine(g, cfg)
    stream.extend(256)
    rng = np.random.default_rng(23)
    stream.apply_delta(random_delta(stream.graph, rng, inserts=2,
                                    deletes=2, reweights=2))
    assert stream.stale > 0
    with tempfile.TemporaryDirectory() as d:
        stream.snapshot(d)
        restored = StreamEngine(stream.graph, cfg)
        assert restored.restore(d)
    assert restored.stale == stream.stale and restored.epoch == 1
    assert restored.refresh() == 0
    _assert_stream_equals_fresh(restored, cfg, k=4)


def test_stream_restore_returns_false_when_empty():
    g = small_graph()
    with tempfile.TemporaryDirectory() as d:
        assert not StreamEngine(g, IMMConfig(batch=32)).restore(d)


def test_stream_restore_rejects_mismatched_batch_or_sampler():
    """Saved batch keys only reproduce their rows under the identical
    sampler composition and batch width — a mismatched restore must fail
    loudly, not silently corrupt same-key repair."""
    g = small_graph()
    stream = StreamEngine(g, IMMConfig(batch=64, seed=1))
    stream.extend(128)
    with tempfile.TemporaryDirectory() as d:
        stream.snapshot(d)
        with pytest.raises(ValueError, match="batch"):
            StreamEngine(g, IMMConfig(batch=32, seed=1)).restore(d)
        with pytest.raises(ValueError, match="sampler"):
            StreamEngine(g, IMMConfig(batch=64, seed=1,
                                      backend="sparse")).restore(d)
        # ... and against the graph identity: resident rows sampled on
        # one edge set are not valid against another
        stream.apply_delta(random_delta(
            stream.graph, np.random.default_rng(24), deletes=2))
        with pytest.raises(ValueError, match="different graph"):
            StreamEngine(stream.graph,
                         IMMConfig(batch=64, seed=1)).restore(d)


# --------------------------------------------------------------- IMServer ----

def test_imserver_result_ordering_out_of_order_sizes():
    """Tickets map to their own answers under mixed seed-set sizes and
    multiple chunks (padding/batching never permutes results)."""
    g = small_graph()
    engine = InfluenceEngine(g, IMMConfig(k=4, batch=64, max_theta=256))
    engine.extend(256)
    server = IMServer(engine, max_batch=4)    # force several chunks
    rng = np.random.default_rng(18)
    sets = [rng.choice(g.n, size=s, replace=False)
            for s in (5, 1, 7, 2, 3, 1, 6, 4, 2, 5)]
    tickets = [server.submit(s) for s in sets]
    got = server.flush()
    assert server.pending == 0 and len(got) == len(sets)
    want = engine.influences(sets)
    for t, w in zip(tickets, want):
        assert got[t] == pytest.approx(float(w), rel=1e-6)


def test_imserver_background_refresh_epoch_consistency():
    """A flush spanning an apply_delta answers every ticket from one
    epoch (identical sets -> identical sigma), and the budgeted
    background refresh drains staleness between flushes."""
    g = small_graph()
    cfg = IMMConfig(k=4, batch=64, max_theta=512, seed=2)
    stream = StreamEngine(g, cfg)
    stream.extend(256)
    server = IMServer(stream, max_batch=4, refresh_budget=96)
    probe = np.asarray(stream.select(4).seeds)
    t0 = server.submit(probe)
    rng = np.random.default_rng(19)
    server.apply_delta(random_delta(stream.graph, rng, deletes=4,
                                    inserts=4))
    t1 = server.submit(probe)                 # same set, post-delta submit
    t2 = server.submit(probe)
    got = server.flush()
    # no torn read: all three answered against the same (post-delta) state
    assert got[t0] == got[t1] == got[t2]
    assert server.served_epoch == 1
    # background refresh drains between flushes without explicit calls
    for _ in range(32):
        if stream.stale == 0:
            break
        server.influence(probe)               # each flush repairs a slice
    assert stream.stale == 0
    # drained server answers == fresh engine on the current graph
    fresh = InfluenceEngine(stream.graph, stream.cfg)
    fresh.extend(stream.theta)
    assert server.influence(probe) == pytest.approx(
        fresh.influence(probe), rel=1e-6)


def test_imserver_async_refresh_worker_epoch_consistency():
    """The threaded refresh worker (ROADMAP: a true async IMServer
    queue): repair runs on a background thread *between* flushes, every
    flush stays epoch-consistent (identical sets in one flush ->
    identical sigma, no torn reads against the concurrent worker), the
    backlog drains with NO refresh calls from the serving path, and the
    drained store equals a fresh engine on the post-delta graph."""
    g = small_graph()
    cfg = IMMConfig(k=4, batch=64, max_theta=512, seed=3)
    stream = StreamEngine(g, cfg)
    stream.extend(256)
    with IMServer(stream, max_batch=4, refresh_budget=64,
                  async_refresh=True) as server:
        assert server.async_refreshing
        probe = np.asarray(server.select(4).seeds)
        rng = np.random.default_rng(20)
        for _ in range(3):            # several epochs under live repair
            t0 = server.submit(probe)
            server.apply_delta(random_delta(stream.graph, rng, deletes=3,
                                            inserts=3, reweights=2))
            t1 = server.submit(probe)
            t2 = server.submit(probe)
            got = server.flush()
            # one flush == one epoch: the worker cannot interleave a
            # repair slice (which would change sigma) mid-flush
            assert got[t0] == got[t1] == got[t2]
        # the worker alone drains the backlog — no refresh() from here
        assert server.drain(timeout=60.0)
        assert stream.stale == 0 and server.refreshes_run > 0
        fresh = InfluenceEngine(stream.graph, stream.cfg)
        fresh.extend(stream.theta)
        np.testing.assert_array_equal(np.asarray(stream.store.counter),
                                      np.asarray(fresh.store.counter))
        assert server.influence(probe) == pytest.approx(
            fresh.influence(probe), rel=1e-6)
    assert not server.async_refreshing        # context exit stopped it


def test_imserver_async_refresh_requires_budget():
    g = small_graph()
    stream = StreamEngine(g, IMMConfig(batch=32))
    with pytest.raises(ValueError, match="refresh_budget"):
        IMServer(stream, async_refresh=True)


def test_imserver_rejects_refresh_budget_on_static_engine():
    g = small_graph()
    engine = InfluenceEngine(g, IMMConfig(batch=32))
    with pytest.raises(ValueError, match="StreamEngine"):
        IMServer(engine, refresh_budget=64)
    server = IMServer(engine)
    with pytest.raises(ValueError, match="StreamEngine"):
        server.apply_delta(None)
    # a zero budget could never drain a backlog — refused up front
    stream = StreamEngine(g, IMMConfig(batch=32))
    with pytest.raises(ValueError, match=">= 1"):
        IMServer(stream, refresh_budget=0)
    with pytest.raises(ValueError, match=">= 1"):
        stream.refresh(budget=0)


# --------------------------------------------------- satellite: fail-fast ----

def test_index_store_mesh_fails_fast_with_workaround():
    """Mesh + indices is refused at construction and at snapshot restore
    with a message naming the supported (representation, mesh)
    combinations (used to fail late and obscurely at the first
    select)."""
    g = rmat_graph(48, 256, seed=0)
    with pytest.raises(ValueError, match="bitmap"):
        InfluenceEngine(g, IMMConfig(store="indices"), mesh=theta_mesh())
    idx = make_store("indices", 16)
    idx.add_batch(jnp.asarray(np.eye(4, 16, dtype=np.uint8)))
    # the restore matrix error is one coherent message naming every
    # supported combination, not a single bitmap-only hint
    with pytest.raises(ValueError, match=r"(?s)\(representation, mesh\)"
                                         r".*bitmap.*packed.*compressed"
                                         r".*indices.*without a mesh"):
        store_from_state(idx.state(), mesh=theta_mesh())
    with pytest.raises(ValueError, match="bitmap"):
        StreamEngine(g, IMMConfig(store="indices"), mesh=theta_mesh())
