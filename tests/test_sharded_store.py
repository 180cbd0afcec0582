"""ShardedStore (paper C1 end-to-end): sharded-vs-single-device
equivalence, per-shard growth invariants, true decremental sharded
selection, elastic snapshot/restore, the 2D (theta x vertex) layout
cells, and the forced multi-device subprocess cells.

These tests use meshes over however many devices the process has — 1 in
a plain run, 4 under scripts/ci.sh's
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` pass — and the
subprocess tests always exercise the real 4-shard (1D) and 8-device
2x4 (2D) layouts.
"""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.imm_snap import make_im_mesh, mesh_engine_kwargs
from repro.core.adaptive import l_pad_for
from repro.core.engine import InfluenceEngine, IMMConfig
from repro.core.selection import (
    select_dense, select_dense_sharded, select_sparse_sharded,
)
from repro.core.store import (
    BitmapStore, ShardedStore, make_store, store_from_state,
)
from repro.graphs import balanced_vertex_partition, rmat_graph
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def theta_mesh(shards: int = None):
    return make_mesh((shards or jax.device_count(),), ("data",))


def im_mesh_2d():
    """A 2D theta x vertex mesh over the available devices: (D/2, 2) on
    even device counts (the CI forced-4-device pass -> 2x2), (1, 1) on a
    single device — the full 2D code path runs either way."""
    d = jax.device_count()
    return make_im_mesh((d // 2, 2) if d % 2 == 0 else (d, 1))


def mesh_kw(mesh):
    return mesh_engine_kwargs(mesh)


# ------------------------------------------------------------------ store ----

def test_sharded_store_matches_bitmap_counters_and_hits():
    """Same batches (incl. sizes not divisible by the shard count) into a
    BitmapStore and a ShardedStore: identical count, fused counter,
    coverage stats, and membership query answers."""
    rng = np.random.default_rng(0)
    n = 48
    bs, ss = BitmapStore(n), ShardedStore(n, mesh=theta_mesh())
    for B in (24, 10, 7, 64):
        batch = (rng.random((B, n)) < 0.2).astype(np.uint8)
        bs.add_batch(jnp.asarray(batch))
        ss.add_batch(jnp.asarray(batch))
    assert bs.count == ss.count == 105
    assert ss.count == int(ss.counts.sum())
    np.testing.assert_array_equal(np.asarray(bs.counter),
                                  np.asarray(ss.counter))
    assert bs.coverage_stats() == ss.coverage_stats()
    S = np.asarray([[0, 1, 2], [5, 5, 5], [7, 30, 12]], np.int32)
    np.testing.assert_allclose(np.asarray(bs.hits(S)), np.asarray(ss.hits(S)),
                               rtol=1e-6)


def test_sharded_store_per_shard_growth_and_layout():
    """cap_local is a power of two per shard; every device shard buffer
    is (cap_local, n) — the global arena never lives on one device."""
    n = 32
    ss = ShardedStore(n, mesh=theta_mesh())
    D = ss.D
    assert ss.capacity == D * ss.cap_local
    cap0 = ss.cap_local
    rng = np.random.default_rng(1)
    # force at least one per-shard doubling
    for _ in range(4):
        ss.add_batch(jnp.asarray(
            (rng.random((16 * D, n)) < 0.3).astype(np.uint8)))
    assert ss.cap_local > cap0 and ss.cap_local & (ss.cap_local - 1) == 0
    shards = ss.R.addressable_shards
    local_devices = len(jax.local_devices())
    assert len(shards) == local_devices
    assert all(s.data.shape == (ss.cap_local * D // local_devices, n)
               for s in shards)
    # valid mask counts exactly the stored rows, per shard
    assert int(np.asarray(ss.valid_mask()).sum()) == ss.count


def test_sharded_selection_matches_dense_both_methods():
    """Sharded rebuild AND true-decrement selection over the store's
    native shards == single-device dense selection (permutation-invariant
    exact integer reductions)."""
    rng = np.random.default_rng(2)
    n = 40
    mesh = theta_mesh()
    bs, ss = BitmapStore(n), ShardedStore(n, mesh=mesh)
    for B in (24, 9, 31):
        batch = (rng.random((B, n)) < 0.25).astype(np.uint8)
        bs.add_batch(jnp.asarray(batch))
        ss.add_batch(jnp.asarray(batch))
    vd, vs = bs.view(), ss.view()
    for method in ("rebuild", "decrement"):
        s1, f1, g1 = select_dense(vd.R, vd.valid, 6, method)
        s2, f2, g2 = select_dense_sharded(
            mesh, vs.R, vs.valid, 6, theta_axes=("data",), method=method)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        assert float(f1) == pytest.approx(float(f2))
    with pytest.raises(ValueError):
        select_dense_sharded(mesh, vs.R, vs.valid, 2, method="nope")


def test_sharded_store_state_roundtrips_across_layouts():
    rng = np.random.default_rng(3)
    n, mesh = 36, theta_mesh()
    ss = ShardedStore(n, mesh=mesh)
    ss.add_batch(jnp.asarray((rng.random((50, n)) < 0.3).astype(np.uint8)))
    st = ss.state()
    assert str(np.asarray(st["kind"])) == "sharded"
    assert st["R"].shape == (50, n)          # compact valid rows only
    # sharded -> sharded (same mesh)
    clone = store_from_state(st, mesh=mesh)
    assert isinstance(clone, ShardedStore) and clone.count == 50
    np.testing.assert_array_equal(np.asarray(clone.counter),
                                  np.asarray(ss.counter))
    # sharded -> single-device bitmap
    flat = store_from_state(st)
    assert isinstance(flat, BitmapStore) and flat.count == 50
    np.testing.assert_array_equal(np.asarray(flat.counter),
                                  np.asarray(ss.counter))
    # bitmap -> sharded
    resharded = store_from_state(flat.state(), mesh=mesh)
    assert isinstance(resharded, ShardedStore) and resharded.count == 50
    np.testing.assert_array_equal(np.asarray(resharded.counter),
                                  np.asarray(ss.counter))
    # index snapshots cannot land on a mesh
    idx = make_store("indices", n)
    idx.add_batch(jnp.asarray((rng.random((8, n)) < 0.1).astype(np.uint8)))
    with pytest.raises(ValueError):
        store_from_state(idx.state(), mesh=mesh)


def test_make_store_sharded_requires_mesh():
    assert isinstance(make_store("sharded", 16, mesh=theta_mesh()),
                      ShardedStore)
    with pytest.raises(TypeError):
        make_store("sharded", 16)
    with pytest.raises(ValueError):
        InfluenceEngine(rmat_graph(32, 64, seed=0),
                        IMMConfig(store="sharded"))


# ----------------------------------------------------------------- engine ----

def test_engine_sharded_run_seed_for_seed_equals_dense():
    """The headline C1 invariant through the whole engine: run() on a
    mesh == run() without one, bit for bit, for a fixed cfg.seed."""
    g = rmat_graph(128, 1024, seed=4)
    cfg = IMMConfig(k=5, batch=64, max_theta=256, seed=3)
    dense = InfluenceEngine(g, cfg)
    sharded = InfluenceEngine(g, cfg, mesh=theta_mesh())
    assert isinstance(sharded.store, ShardedStore)
    r1, r2 = dense.run(), sharded.run()
    np.testing.assert_array_equal(r1.seeds, r2.seeds)
    np.testing.assert_array_equal(r1.counter, r2.counter)
    assert r1.theta == r2.theta
    assert r1.covered_frac == pytest.approx(r2.covered_frac)
    np.testing.assert_allclose(
        dense.influences([r1.seeds[:2], r1.seeds]),
        sharded.influences([r1.seeds[:2], r1.seeds]), rtol=1e-6)


def test_engine_sharded_snapshot_restore_seed_for_seed():
    """Snapshot on a mesh, restore on a mesh / no mesh: selections and
    the continued sample stream stay identical to the dense engine."""
    g = rmat_graph(96, 768, seed=5)
    cfg = IMMConfig(k=4, batch=32, max_theta=128, seed=11)
    mesh = theta_mesh()
    dense = InfluenceEngine(g, cfg)
    sharded = InfluenceEngine(g, cfg, mesh=mesh)
    dense.extend(128)
    sharded.extend(128)
    want = dense.select(4)
    with tempfile.TemporaryDirectory() as d:
        sharded.snapshot(d)
        again = InfluenceEngine(g, cfg, mesh=mesh)
        assert again.restore(d)
        np.testing.assert_array_equal(again.select(4).seeds, want.seeds)
        flat = InfluenceEngine(g, cfg)
        assert flat.restore(d)
        assert isinstance(flat.store, BitmapStore)
        np.testing.assert_array_equal(flat.select(4).seeds, want.seeds)
        # the restored PRNG stream continues identically across layouts
        dense.extend(dense.theta + 64)
        again.extend(again.theta + 64)
        np.testing.assert_array_equal(
            np.asarray(dense.store.counter), np.asarray(again.store.counter))


def test_engine_prebuilt_sharded_store_implies_mesh():
    g = rmat_graph(64, 512, seed=6)
    store = ShardedStore(g.n, mesh=theta_mesh())
    engine = InfluenceEngine(g, IMMConfig(k=3, batch=32), store=store)
    assert engine.mesh is store.mesh
    engine.extend(64)
    sel = engine.select(3)
    assert len(set(sel.seeds.tolist())) == 3


# --------------------------------------------------- 2D (theta x vertex) ----

def test_2d_store_matches_bitmap_counters_and_hits():
    """Same batches into a BitmapStore and a 2D ShardedStore (vertex
    axis resident): identical count, fused counter, coverage stats, and
    membership answers — including an n not divisible by Dv (the padded
    columns must stay invisible)."""
    rng = np.random.default_rng(10)
    n = 49                      # odd over Dv=2 -> n_local 25, n_pad 50
    bs = BitmapStore(n)
    ss = make_store("sharded", n, mesh=im_mesh_2d(), vertex_axis="vertex")
    assert ss.n_pad == ss.Dv * ss.n_local >= n
    for B in (24, 10, 7, 64):
        batch = (rng.random((B, n)) < 0.2).astype(np.uint8)
        bs.add_batch(jnp.asarray(batch))
        ss.add_batch(jnp.asarray(batch))
    assert bs.count == ss.count == 105
    np.testing.assert_array_equal(np.asarray(bs.counter),
                                  np.asarray(ss.counter))
    assert bs.coverage_stats() == ss.coverage_stats()
    S = np.asarray([[0, 1, 2], [5, 5, 5], [7, 30, 12]], np.int32)
    np.testing.assert_allclose(np.asarray(bs.hits(S)), np.asarray(ss.hits(S)),
                               rtol=1e-6)


def test_2d_per_device_buffer_shapes():
    """The 2D acceptance invariant: every device buffer is
    (cap_local, n_local) — n/Dv vertex columns, never the full (theta, n)
    arena — for R, sizes, and the counter partials."""
    n = 64
    mesh = im_mesh_2d()
    ss = ShardedStore(n, mesh=mesh, vertex_axis="vertex")
    rng = np.random.default_rng(11)
    for _ in range(3):
        ss.add_batch(jnp.asarray(
            (rng.random((16 * ss.D, n)) < 0.3).astype(np.uint8)))
    n_devs = len(jax.local_devices())
    shards = ss.R.addressable_shards
    assert len(shards) == n_devs
    assert all(s.data.shape == (ss.cap_local, ss.n_local) for s in shards)
    assert all(s.data.shape == (1, ss.n_local)
               for s in ss._counter.addressable_shards)
    if ss.Dv > 1:
        assert ss.n_local < n          # columns genuinely split
    assert int(np.asarray(ss.valid_mask()).sum()) == ss.count


def test_2d_selection_matches_dense_dense_and_sparse():
    """2D sharded rebuild/decrement selection — dense bitmaps AND the
    sharded-sparse index-list strategy — equals single-device dense
    selection bit for bit."""
    rng = np.random.default_rng(12)
    n = 41
    mesh = im_mesh_2d()
    bs = BitmapStore(n)
    ss = ShardedStore(n, mesh=mesh, vertex_axis="vertex")
    for B in (24, 9, 31):
        batch = (rng.random((B, n)) < 0.25).astype(np.uint8)
        bs.add_batch(jnp.asarray(batch))
        ss.add_batch(jnp.asarray(batch))
    vd, vs = bs.view(), ss.view()
    iv = ss.index_view(l_pad_for(ss.max_local_size()))
    for method in ("rebuild", "decrement"):
        s1, f1, g1 = select_dense(vd.R, vd.valid, 6, method)
        s2, f2, g2 = select_dense_sharded(
            mesh, vs.R, vs.valid, 6, theta_axes=("data",),
            vertex_axis="vertex", method=method, n=n)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        assert float(f1) == pytest.approx(float(f2))
        s3, f3, g3 = select_sparse_sharded(
            mesh, iv.R, iv.valid, n, 6, theta_axes=("data",),
            vertex_axis="vertex", method=method)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s3))
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g3))


def test_2d_engine_run_seed_for_seed_equals_dense():
    """The headline 2D invariant through the whole engine: run() on a
    theta x vertex mesh == run() without one, bit for bit."""
    g = rmat_graph(128, 1024, seed=4)
    cfg = IMMConfig(k=5, batch=64, max_theta=256, seed=3)
    dense = InfluenceEngine(g, cfg)
    sharded = InfluenceEngine(g, cfg, **mesh_kw(im_mesh_2d()))
    assert isinstance(sharded.store, ShardedStore)
    assert sharded.vertex_axis == "vertex"
    r1, r2 = dense.run(), sharded.run()
    np.testing.assert_array_equal(r1.seeds, r2.seeds)
    np.testing.assert_array_equal(r1.counter, r2.counter)
    assert r1.theta == r2.theta
    np.testing.assert_allclose(
        dense.influences([r1.seeds[:2], r1.seeds]),
        sharded.influences([r1.seeds[:2], r1.seeds]), rtol=1e-6)


def test_2d_engine_adaptive_sharded_sparse_selection():
    """When C4 chooses indices on a mesh engine (low coverage, per-
    vertex-shard threshold), selection routes through the sharded-sparse
    strategy and still matches the single-device answer."""
    g = rmat_graph(256, 512, seed=8, weighted_ic="wc")   # tiny RRR sets
    # switch_ratio=2: indices wins once l_max * 2 < n_local, which holds
    # for this graph on every vertex-shard count the CI runs (1 and 2)
    cfg = IMMConfig(k=4, batch=64, max_theta=256, seed=9,
                    sparse_rep_min_n=1, backend="sparse", switch_ratio=2)
    dense = InfluenceEngine(g, cfg)
    sharded = InfluenceEngine(g, cfg, **mesh_kw(im_mesh_2d()))
    dense.extend(256)
    sharded.extend(256)
    a, b = dense.select(4), sharded.select(4)
    np.testing.assert_array_equal(a.seeds, b.seeds)
    assert b.representation == "indices"   # the C4 sparse path engaged


def test_cross_layout_snapshot_roundtrips_2d():
    """Snapshots are elastic across {none, 1D, 2D}: every pair restores
    with identical counters and selections, and the restored PRNG stream
    continues identically (the S3 acceptance cell)."""
    g = rmat_graph(96, 768, seed=5)
    cfg = IMMConfig(k=4, batch=32, max_theta=128, seed=11)
    mesh1, mesh2 = theta_mesh(), im_mesh_2d()
    engines = {
        "none": InfluenceEngine(g, cfg),
        "1d": InfluenceEngine(g, cfg, mesh=mesh1),
        "2d": InfluenceEngine(g, cfg, **mesh_kw(mesh2)),
    }
    for e in engines.values():
        e.extend(128)
    want = engines["none"].select(4)
    layouts = {
        "none": {}, "1d": {"mesh": mesh1}, "2d": mesh_kw(mesh2),
    }
    for src_name, src in engines.items():
        with tempfile.TemporaryDirectory() as d:
            src.snapshot(d)
            for dst_name, kw in layouts.items():
                dst = InfluenceEngine(g, cfg, **kw)
                assert dst.restore(d), (src_name, dst_name)
                np.testing.assert_array_equal(
                    dst.select(4).seeds, want.seeds)
                np.testing.assert_array_equal(
                    np.asarray(dst.store.counter),
                    np.asarray(src.store.counter))
                # the restored stream continues identically
                dst.extend(dst.theta + 32)
                ref = InfluenceEngine(g, cfg)
                ref.extend(128 + 32)
                np.testing.assert_array_equal(
                    np.asarray(dst.store.counter),
                    np.asarray(ref.store.counter))


# ------------------------------------------------ balanced vertex layout ----

def skewed_partition(n, dv, seed=13):
    """An edge-balanced partition from a genuinely skewed dst stream, so
    the block boundaries land away from the equal-block cuts."""
    rng = np.random.default_rng(seed)
    dst = np.minimum(rng.geometric(4.0 / n, size=8 * n), n - 1)
    return balanced_vertex_partition(n, dv, dst=dst)


def test_2d_balanced_store_matches_bitmap():
    """A balanced-layout ShardedStore answers every read — counter,
    coverage stats, membership hits, reverse touch — identically to a
    BitmapStore and to the equal-layout store, for an n whose balanced
    blocks are uneven and padded."""
    rng = np.random.default_rng(14)
    n, mesh = 49, im_mesh_2d()
    dv = mesh.shape["vertex"]
    part = skewed_partition(n, dv)
    bs = BitmapStore(n)
    eq = ShardedStore(n, mesh=mesh, vertex_axis="vertex")
    bal = ShardedStore(n, mesh=mesh, vertex_axis="vertex", partition=part)
    assert bal.partition is part
    assert bal.n_local == part.block and bal.n_pad == part.n_pad
    for B in (24, 10, 7, 64):
        batch = (rng.random((B, n)) < 0.2).astype(np.uint8)
        for s in (bs, eq, bal):
            s.add_batch(jnp.asarray(batch))
    assert bs.count == bal.count
    np.testing.assert_array_equal(np.asarray(bs.counter),
                                  np.asarray(bal.counter))
    assert bs.coverage_stats() == bal.coverage_stats()
    S = np.asarray([[0, 1, 2], [5, 5, 5], [7, 30, 12], [48, 48, 48]],
                   np.int32)
    np.testing.assert_allclose(np.asarray(bs.hits(S)),
                               np.asarray(bal.hits(S)), rtol=1e-6)
    # reverse touch: same row mask as the equal layout, vertex by vertex
    verts = jnp.asarray([0, 17, 48, 5], jnp.int32)
    vmask = jnp.asarray([True, True, True, False])
    np.testing.assert_array_equal(
        np.asarray(eq.rows_touching_cols(verts, vmask)),
        np.asarray(bal.rows_touching_cols(verts, vmask)))


@pytest.mark.parametrize("layout", ["equal", "balanced"])
def test_2d_read_sets_and_reset(layout):
    """Sets read back by their number in write order (batch b, row r is
    set b * B + r, whatever shard holds it) are the BitmapStore's rows,
    for batch sizes the shard count does not divide and on both vertex
    layouts, and stay so when the tiles grow ahead of a write; emptied
    in place, the store equals a fresh one (arena,
    sizes, live bits, counter, counts, capacity) with a later version,
    and fills again from set 0."""
    rng = np.random.default_rng(21)
    n, mesh = 49, im_mesh_2d()
    part = (skewed_partition(n, mesh.shape["vertex"])
            if layout == "balanced" else None)
    bs = BitmapStore(n)
    sh = ShardedStore(n, mesh=mesh, vertex_axis="vertex", partition=part)
    batches = [(rng.random((B, n)) < 0.2).astype(np.uint8)
               for B in (24, 7, 64, 10)]
    for batch in batches:
        bs.add_batch(jnp.asarray(batch))
        sh.add_batch(jnp.asarray(batch))
    want = np.asarray(bs.R[:bs.count])
    np.testing.assert_array_equal(sh.read_sets(np.arange(bs.count)), want)
    pick = np.asarray([104, 0, 30, 31, 24, 63])
    np.testing.assert_array_equal(sh.read_sets(pick), want[pick])
    with pytest.raises(IndexError):
        sh.read_sets([bs.count])
    # grown ahead of a write, by a rung: the sets stay where they were read
    cap = sh.capacity
    sh.reserve(cap - sh.count + 1)
    assert sh.capacity == 2 * cap and sh.count == bs.count
    np.testing.assert_array_equal(sh.read_sets(np.arange(bs.count)), want)

    version = sh.version
    sh.reset()
    fresh = ShardedStore(n, mesh=mesh, vertex_axis="vertex", partition=part)
    for a in ("R", "sizes", "live", "_counter", "_counts", "counts",
              "counter"):
        np.testing.assert_array_equal(np.asarray(getattr(sh, a)),
                                      np.asarray(getattr(fresh, a)))
    assert (sh.count, sh.capacity) == (0, fresh.capacity)
    assert sh.version > version
    sh.add_batch(jnp.asarray(batches[1]))
    np.testing.assert_array_equal(sh.read_sets(np.arange(7)), batches[1])


def test_read_sets_refuses_after_rows_moved():
    """A compaction moves rows, so sets lose their write order."""
    n, mesh = 16, im_mesh_2d()
    sh = ShardedStore(n, mesh=mesh, vertex_axis="vertex")
    sh.add_batch(jnp.ones((8, n), jnp.uint8))
    sh.kill_rows(np.arange(sh.capacity) == 0)
    sh.compact()
    with pytest.raises(ValueError):
        sh.read_sets([0])
    sh.reset()
    sh.add_batch(jnp.ones((4, n), jnp.uint8))
    assert sh.read_sets([3]).sum() == n


def test_2d_balanced_selection_matches_dense():
    """Balanced-layout sharded selection — rebuild/decrement, dense
    bitmaps AND the C4 sharded-sparse index view — equals single-device
    dense selection bit for bit (the boundaries move, the argmax
    tie-break cannot)."""
    rng = np.random.default_rng(15)
    n, mesh = 41, im_mesh_2d()
    part = skewed_partition(n, mesh.shape["vertex"], seed=16)
    bs = BitmapStore(n)
    ss = ShardedStore(n, mesh=mesh, vertex_axis="vertex", partition=part)
    for B in (24, 9, 31):
        batch = (rng.random((B, n)) < 0.25).astype(np.uint8)
        bs.add_batch(jnp.asarray(batch))
        ss.add_batch(jnp.asarray(batch))
    vd, vs = bs.view(), ss.view()
    iv = ss.index_view(l_pad_for(ss.max_local_size()))
    for method in ("rebuild", "decrement"):
        s1, f1, g1 = select_dense(vd.R, vd.valid, 6, method)
        s2, f2, g2 = select_dense_sharded(
            mesh, vs.R, vs.valid, 6, theta_axes=("data",),
            vertex_axis="vertex", method=method, n=n, partition=part)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        assert float(f1) == pytest.approx(float(f2))
        s3, f3, g3 = select_sparse_sharded(
            mesh, iv.R, iv.valid, n, 6, theta_axes=("data",),
            vertex_axis="vertex", method=method, partition=part)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s3))
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g3))


def test_balanced_snapshot_roundtrips_elastically():
    """`state()` returns rows in global vertex order whatever the column
    layout, so snapshots restore across equal <-> balanced <-> bitmap
    with identical counters (the re-partitioning contract)."""
    rng = np.random.default_rng(17)
    n, mesh = 36, im_mesh_2d()
    part = skewed_partition(n, mesh.shape["vertex"], seed=18)
    bal = ShardedStore(n, mesh=mesh, vertex_axis="vertex", partition=part)
    bal.add_batch(jnp.asarray((rng.random((50, n)) < 0.3).astype(np.uint8)))
    st = bal.state()
    assert st["R"].shape == (50, n)          # global order, pads stripped
    want = np.asarray(bal.counter)
    # balanced -> single-device bitmap
    flat = store_from_state(st)
    assert isinstance(flat, BitmapStore)
    np.testing.assert_array_equal(np.asarray(flat.counter), want)
    # balanced -> equal-layout sharded
    eq = store_from_state(st, mesh=mesh, vertex_axis="vertex")
    assert eq.partition.is_equal
    np.testing.assert_array_equal(np.asarray(eq.counter), want)
    # equal -> balanced (fresh boundaries) and balanced -> balanced
    for src in (eq.state(), st):
        back = store_from_state(src, mesh=mesh, vertex_axis="vertex",
                                partition=part)
        assert back.partition is part
        np.testing.assert_array_equal(np.asarray(back.counter), want)


def test_2d_engine_adaptive_sparse_with_balanced_partition():
    """The C4 indices representation composes with the balanced layout:
    local index lists convert through the data-dependent block starts
    and still match the single-device answer."""
    g = rmat_graph(256, 512, seed=8, weighted_ic="wc")
    cfg = IMMConfig(k=4, batch=64, max_theta=256, seed=9,
                    sparse_rep_min_n=1, backend="sparse", switch_ratio=2,
                    partition="balanced")
    dense = InfluenceEngine(g, cfg)     # partition is inert off-mesh
    sharded = InfluenceEngine(g, cfg, **mesh_kw(im_mesh_2d()))
    assert not sharded.store.partition.is_equal
    dense.extend(256)
    sharded.extend(256)
    a, b = dense.select(4), sharded.select(4)
    np.testing.assert_array_equal(a.seeds, b.seeds)
    assert b.representation == "indices"   # the C4 sparse path engaged


def test_make_im_mesh_and_engine_kwargs():
    """--mesh spellings resolve as documented and clip gracefully."""
    assert make_im_mesh(None) is None and make_im_mesh(0) is None
    m1 = make_im_mesh(2)
    assert tuple(m1.axis_names) == ("data",)
    assert mesh_engine_kwargs(m1) == {"mesh": m1, "theta_axes": ("data",)}
    m2 = make_im_mesh("2x2")
    assert tuple(m2.axis_names) == ("data", "vertex")
    kw = mesh_engine_kwargs(m2)
    assert kw["theta_axes"] == ("data",) and kw["vertex_axis"] == "vertex"
    # pod-sized 2D flags clip to the local device count, vertex first:
    # theta sharding survives, the vertex axis shrinks into what's left
    d = jax.device_count()
    big = make_im_mesh(f"{d}x1024")
    assert big.shape["data"] == d and big.shape["vertex"] == 1
    big = make_im_mesh("1024x1024")
    assert int(np.prod([big.shape[a] for a in big.axis_names])) <= d
    assert big.shape["data"] == d      # theta won the clip
    # a Mesh passes through; tuples spell 2D too
    assert make_im_mesh(m2) is m2
    mt = make_im_mesh((1, 1))
    assert tuple(mt.axis_names) == ("data", "vertex")
    assert mesh_engine_kwargs(None) == {}
    with pytest.raises(ValueError):
        make_im_mesh("0x2")


# ---------------------------------------- forced multi-device subprocess ----

def _run_force_mesh(devices: int, mesh: str, *extra: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # drop any inherited device-count flag (the CI mesh pass exports =4;
    # XLA lets the later flag win, which would shrink our forced mesh)
    inherited = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + inherited).strip()
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "force_mesh_check.py"),
         "--mesh", mesh, *extra],
        env=env, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_store_forced_4dev_subprocess():
    """The 1D C1 acceptance cell: under a forced 4-device host platform
    the arena is physically split into 4 (cap_local, n) buffers and
    results stay seed-for-seed identical to BitmapStore + dense selection
    (see tests/force_mesh_check.py for the assertions)."""
    out = _run_force_mesh(4, "4")
    assert out["ok"] and out["devices"] == 4


def test_sharded_store_forced_8dev_2x4_subprocess():
    """The 2D acceptance cell: a forced-8-device 2x4 mesh splits the
    arena into 8 (cap_local, n/4) tiles — theta over 2 shards, vertices
    over 4 — and select(k)/influence(S) stay bitwise identical to the
    single-device engine (the full (theta, n) arena never exists on one
    device)."""
    out = _run_force_mesh(8, "2x4")
    assert out["ok"] and out["devices"] == 8
    assert out["n_local"] == 32        # ceil(128 / 4) vertex columns


def test_sparse_sampler_forced_4dev_2x2_subprocess():
    """The sparse backend's per-device pull on a forced-4-device 2x2
    mesh: each device pulls into its own vertex block over its window of
    the CSR edges, and every answer stays bitwise the single-device
    engine's (balanced blocks, overlap off, fused chain off included)."""
    out = _run_force_mesh(4, "2x2", "--backend", "sparse")
    assert out["ok"] and out["devices"] == 4
    assert out["sampler"] == "IC/sparse" and out["n_local"] == 64
