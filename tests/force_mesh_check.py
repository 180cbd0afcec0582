"""Subprocess body for the forced multi-device ShardedStore checks.

Run by tests/test_sharded_store.py (and scripts/ci.sh) with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` so the mesh code
paths execute on real (host-platform) multi-device buffers even on
CPU-only runners.  ``--mesh RxC`` selects the layout (default ``4`` —
the historical 1D cell; ci.sh also runs ``--mesh 2x4`` under 8 forced
devices).  Asserts the C1 acceptance criteria:

  * the full ``(theta, n)`` arena never materializes on one device —
    per-device buffer shapes are ``(cap_local, n_local)`` with
    ``n_local = ceil(n / Dv)`` vertex columns (``n_local == n`` only on
    1D meshes);
  * sharded ``select(k)`` and ``influence(S)`` are seed-for-seed
    identical to ``BitmapStore`` + dense selection for a fixed
    ``cfg.seed``, including the true decremental sharded strategy;
  * edge-balanced vertex blocks (``cfg.partition="balanced"``) and
    overlap-off traversal (``cfg.overlap=False``) are bitwise identical
    to the equal/overlapped run — layout and scheduling never change an
    answer — and on the 2D rmat cell the balanced layout reports
    strictly lower per-tile edge imbalance;
  * snapshot/restore round-trips across layouts (this mesh -> 1D -> 1
    shard -> none) without changing answers;
  * sets read back in write order (`ShardedStore.read_sets`) are the
    single-device arena's rows, and an arena emptied in place
    (`ShardedStore.reset`) equals a fresh one and fills on;
  * the fused sample->write->count chain (``fused_pipeline="auto"``, the
    default) is bitwise identical to an explicitly-unfused run, and the
    sharded ``rebuild`` and ``decrement`` selections give the
    single-device selection's seeds, gains and covered_frac — including
    on the balanced 2D layout, where pad-column masks and partition
    offsets must not perturb either.

Prints one JSON line on success (consumed by the pytest wrapper).
"""
import argparse
import dataclasses
import json
import sys
import tempfile

import numpy as np
import jax

from repro.configs.imm_snap import make_im_mesh, mesh_engine_kwargs
from repro.core.engine import InfluenceEngine, IMMConfig
from repro.core.selection import get_selection
from repro.core.store import BitmapStore, ShardedStore
from repro.graphs import balance_report, rmat_graph
from repro.launch.mesh import make_mesh
from test_imm_core import _numpy_greedy


def assert_same_selection(eng, dense, k=5):
    """Both rules over the meshed engine's arena tiles (``sharded``) and
    over its tile-local index lists (``sharded-sparse``) give the plain
    greedy's seeds, gains and covered_frac over the single-device
    engine's rows."""
    view = dense.store.view()
    R, valid = np.asarray(view.R), np.asarray(view.valid)
    seeds, gains = _numpy_greedy(R, valid, k)
    frac = float(np.float32(sum(gains)) / np.float32(max(valid.sum(), 1)))
    st = eng.store
    # lists as wide as the longest tile-local set: the pow2 width the
    # engine pads to can pass a narrow balanced tile's column count
    views = {"sharded": st.view(),
             "sharded-sparse": st.index_view(st.max_local_size())}
    for method in ("rebuild", "decrement"):
        for layout, v in views.items():
            s, f, g = get_selection(method, layout)(
                v, k, mesh=eng.mesh, theta_axes=eng.theta_axes,
                vertex_axis=eng.vertex_axis, partition=st.partition,
                codec=st.codec)
            np.testing.assert_array_equal(np.asarray(s), seeds)
            np.testing.assert_array_equal(np.asarray(g), gains)
            assert float(f) == frac, (method, layout, float(f), frac)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="4",
                    help="layout to check: an int (1D) or 'RxC' (2D)")
    ap.add_argument("--store", default="auto",
                    choices=("auto", "packed", "compressed"),
                    help="at-rest arena format for the sharded engine "
                         "('auto' = bitmap tiles; 'packed'/'compressed' "
                         "run the IMPack codecs on every mesh tile)")
    ap.add_argument("--backend", default=None, choices=("dense", "sparse"),
                    help="traversal backend of every engine (default: "
                         "the engine's choice, dense at this n)")
    args = ap.parse_args(argv)

    mesh = make_im_mesh(args.mesh)
    n_dev = jax.device_count()
    want = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    assert n_dev == want, \
        f"mesh {args.mesh} wants {want} forced host devices, got {n_dev}"
    kw = mesh_engine_kwargs(mesh)

    g = rmat_graph(128, 1024, seed=4)
    cfg = IMMConfig(k=5, batch=64, max_theta=256, seed=3,
                    store=args.store, backend=args.backend)
    # the reference stays a single-device bitmap: the IMPack formats
    # must match IT, not just each other
    cfg_dense = dataclasses.replace(cfg, store="auto")

    dense = InfluenceEngine(g, cfg_dense)
    sharded = InfluenceEngine(g, cfg, **kw)
    assert isinstance(dense.store, BitmapStore)
    assert isinstance(sharded.store, ShardedStore)
    want_rep = "bitmap" if args.store == "auto" else args.store
    assert sharded.store.representation == want_rep

    r_dense, r_sharded = dense.run(), sharded.run()

    # --- seed-for-seed identity with BitmapStore + dense selection ------
    np.testing.assert_array_equal(r_dense.seeds, r_sharded.seeds)
    np.testing.assert_array_equal(r_dense.counter, r_sharded.counter)
    assert r_dense.theta == r_sharded.theta
    assert abs(r_dense.covered_frac - r_sharded.covered_frac) < 1e-7

    # --- the full arena never exists on one device ----------------------
    st = sharded.store
    shards = st.R.addressable_shards
    assert len(shards) == n_dev
    # per-device tiles are (cap_local, w_local) where w_local is the
    # codec's at-rest width (== n_local bit columns for bitmap tiles)
    assert all(s.data.shape == (st.cap_local, st.w_local) for s in shards), \
        [s.data.shape for s in shards]
    assert st.capacity == st.D * st.cap_local
    assert st.n_pad == st.Dv * st.n_local
    if args.store == "packed":
        # bit-packing actually shrank the resident tile
        assert st.w_local == -(-st.n_local // 8), (st.w_local, st.n_local)
    if st.Dv > 1:
        # 2D: every device holds only its n/Dv vertex columns
        assert st.n_local < g.n, (st.n_local, g.n)
    assert {tuple(s.data.shape) for s in st.sizes.addressable_shards} == \
        {(st.cap_local,)}
    # counter partials are tiled too (one (1, n_local) block per device)
    assert all(s.data.shape == (1, st.n_local)
               for s in st._counter.addressable_shards)

    # --- true decremental sharded strategy == rebuild == dense ----------
    sel_reb = sharded.select(5, method="rebuild")
    sel_dec = sharded.select(5, method="decrement")
    np.testing.assert_array_equal(sel_reb.seeds, sel_dec.seeds)
    np.testing.assert_array_equal(sel_reb.gains, sel_dec.gains)
    np.testing.assert_array_equal(
        sel_dec.seeds, dense.select(5, method="decrement").seeds)

    # --- fused pipeline: auto is the default above — prove it against
    # an explicitly-unfused run, and both sharded selection rules
    # against the single-device selection, on this mesh/store cell
    unfused = InfluenceEngine(
        g, dataclasses.replace(cfg, fused_pipeline="off"), **kw)
    r_unf = unfused.run()
    np.testing.assert_array_equal(r_sharded.seeds, r_unf.seeds)
    np.testing.assert_array_equal(r_sharded.counter, r_unf.counter)
    assert_same_selection(sharded, dense)

    # --- layout & schedule invariance: balanced blocks, overlap off -----
    imb = {"equal": 1.0, "balanced": 1.0}
    if st.Dv > 1:
        bal = InfluenceEngine(
            g, dataclasses.replace(cfg, partition="balanced"), **kw)
        r_bal = bal.run()
        np.testing.assert_array_equal(r_dense.seeds, r_bal.seeds)
        np.testing.assert_array_equal(r_dense.counter, r_bal.counter)
        bst = bal.store
        assert not bst.partition.is_equal
        # boundaries are data-dependent but per-device tiles stay uniform
        assert all(s.data.shape == (bst.cap_local, bst.w_local)
                   for s in bst.R.addressable_shards)
        imb["equal"] = balance_report(g.edge_dst, g.n, st.Dv)["imbalance"]
        imb["balanced"] = balance_report(
            g.edge_dst, g.n, st.Dv, partition=bst.partition)["imbalance"]
        assert imb["balanced"] <= imb["equal"] + 1e-9, imb
        if imb["equal"] > 1.1:
            # rmat degrees are skewed: balancing must actually help
            assert imb["balanced"] < imb["equal"], imb
        # balanced + overlap-off together, still bitwise identical
        both = InfluenceEngine(
            g, dataclasses.replace(cfg, partition="balanced",
                                   overlap=False), **kw)
        np.testing.assert_array_equal(r_dense.seeds, both.run().seeds)
        # fused chain + sharded selection on the balanced 2D layout: the
        # pad-column masks and partition offsets must not perturb either
        bal_unf = InfluenceEngine(
            g, dataclasses.replace(cfg, partition="balanced",
                                   fused_pipeline="off"), **kw)
        r_bal_unf = bal_unf.run()
        np.testing.assert_array_equal(r_bal.seeds, r_bal_unf.seeds)
        np.testing.assert_array_equal(r_bal.counter, r_bal_unf.counter)
        assert_same_selection(bal, dense)
    noov = InfluenceEngine(
        g, dataclasses.replace(cfg, overlap=False), **kw)
    r_noov = noov.run()
    np.testing.assert_array_equal(r_dense.seeds, r_noov.seeds)
    np.testing.assert_array_equal(r_dense.counter, r_noov.counter)

    # --- fused membership queries agree --------------------------------
    queries = [r_dense.seeds[:2], r_dense.seeds]
    np.testing.assert_allclose(
        dense.influences(queries), sharded.influences(queries), rtol=1e-6)

    # --- snapshot/restore across mesh layouts ---------------------------
    with tempfile.TemporaryDirectory() as d:
        sharded.snapshot(d)
        on1d = InfluenceEngine(
            g, cfg, **mesh_engine_kwargs(make_im_mesh(n_dev)))
        assert on1d.restore(d)
        np.testing.assert_array_equal(on1d.select(5).seeds, r_dense.seeds)
        on1 = InfluenceEngine(g, cfg, mesh=make_mesh((1,), ("data",)))
        assert on1.restore(d)
        np.testing.assert_array_equal(on1.select(5).seeds, r_dense.seeds)
        flat = InfluenceEngine(g, cfg)
        assert flat.restore(d)
        # meshless restore keeps the configured at-rest format
        assert flat.store.representation == want_rep
        if args.store == "auto":
            assert isinstance(flat.store, BitmapStore)
        np.testing.assert_array_equal(flat.select(5).seeds, r_dense.seeds)
        # restored engines keep sampling from the snapshotted key stream,
        # identically to the dense engine
        flat.extend(flat.theta + 64)
        back = InfluenceEngine(g, cfg, **kw)
        assert back.restore(d)
        back.extend(back.theta + 64)
        dense.extend(dense.theta + 64)
        np.testing.assert_array_equal(
            np.asarray(dense.store.counter), np.asarray(back.store.counter))
        np.testing.assert_array_equal(
            np.asarray(dense.store.counter), np.asarray(flat.store.counter))

    # --- sets read back in write order; the arena emptied in place ------
    for part in ("equal", "balanced") if st.Dv > 1 else ("equal",):
        eng = InfluenceEngine(
            g, dataclasses.replace(cfg, partition=part), **kw)
        ref = InfluenceEngine(g, cfg_dense)
        eng.extend(128)
        ref.extend(256)
        want = np.asarray(ref.store.R)
        np.testing.assert_array_equal(
            eng.store.read_sets(np.arange(128)), want[:128])
        es = eng.store
        es.reset()
        fresh = ShardedStore(g.n, mesh=es.mesh, theta_axes=es.theta_axes,
                             vertex_axis=es.vertex_axis,
                             partition=es.partition, codec=want_rep)
        for a in ("R", "sizes", "live", "_counter", "_counts", "counts",
                  "counter"):
            np.testing.assert_array_equal(np.asarray(getattr(es, a)),
                                          np.asarray(getattr(fresh, a)))
        assert (es.count, es.capacity, es.codec) == (
            0, fresh.capacity, fresh.codec)
        # the engine keeps sampling its key stream into the emptied arena
        eng.extend(128)
        np.testing.assert_array_equal(
            eng.store.read_sets(np.arange(128)[::-1]), want[128:][::-1])

    print(json.dumps({
        "ok": True, "devices": n_dev, "mesh": args.mesh,
        "store": args.store,
        "sampler": sharded.sampler_name,
        "theta": int(r_sharded.theta),
        "cap_local": int(st.cap_local), "n_local": int(st.n_local),
        "counts": [int(c) for c in st.counts],
        "imbalance": imb,
    }))


if __name__ == "__main__":
    sys.exit(main())
