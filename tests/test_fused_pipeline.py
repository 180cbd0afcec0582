"""Fused sample->write->count chain vs the legacy two-call path, and
selection over the stores it fills vs the plain greedy: every
comparison here is bitwise (exact array equality, exact float
equality), because the fused pipeline's contract is seed-for-seed
identity, not statistical agreement."""
import numpy as np
import jax
import pytest

from repro.core.engine import IMMConfig, InfluenceEngine
from repro.core.selection import get_selection
from repro.graphs import rmat_graph
from test_imm_core import _numpy_greedy

N, M, K, BATCH, THETA = 128, 1024, 4, 64, 192


def _graph():
    return rmat_graph(N, M, seed=5)


def _pair(store="auto", mesh_kwargs=None, theta=THETA, **cfg_kw):
    """(legacy engine, fused engine) extended with identical seeds."""
    g = _graph()
    engines = []
    for fp in ("off", "auto"):
        cfg = IMMConfig(k=K, batch=BATCH, max_theta=1024, seed=3,
                        store=store, fused_pipeline=fp, **cfg_kw)
        e = InfluenceEngine(g, cfg, **(mesh_kwargs or {}))
        e.extend(theta)
        engines.append(e)
    return engines


def _assert_bitwise(off, on):
    assert off.store.count == on.store.count
    np.testing.assert_array_equal(np.asarray(off.store.counter),
                                  np.asarray(on.store.counter))
    np.testing.assert_array_equal(
        np.asarray(off.store.sizes)[:off.store.count],
        np.asarray(on.store.sizes)[:on.store.count])
    s_off, s_on = off.select(K), on.select(K)
    np.testing.assert_array_equal(np.asarray(s_off.seeds),
                                  np.asarray(s_on.seeds))
    assert float(s_off.covered_frac) == float(s_on.covered_frac)
    assert float(s_off.influence) == float(s_on.influence)
    # the PRNG stream stayed aligned batch-for-batch
    np.testing.assert_array_equal(np.asarray(off.key), np.asarray(on.key))


# ------------------------------------------------------ single-device chain


@pytest.mark.parametrize("store", ["auto", "packed"])
def test_fused_matches_legacy(store):
    off, on = _pair(store=store)
    _assert_bitwise(off, on)


@pytest.mark.parametrize("model", ["WC", "GT"])
def test_fused_matches_legacy_models(model):
    off, on = _pair(model=model)
    _assert_bitwise(off, on)


@pytest.mark.parametrize("store", ["auto", "packed"])
def test_fused_matches_legacy_interpret(store):
    """cfg.pallas_interpret routes the chain's arena_commit through the
    Pallas interpreter on CPU — still bitwise-equal to the legacy path."""
    off, on = _pair(store=store, pallas_interpret=True)
    _assert_bitwise(off, on)


def test_compressed_store_falls_back_bitwise():
    """Token-compressed tiles are outside the chain; the extender must
    decline and hand the SAME batch key to the legacy path, so the
    stream is preserved across the fused/unfused boundary."""
    off, on = _pair(store="compressed")
    assert on._fused is not None  # built, but declining per batch
    _assert_bitwise(off, on)


def test_fused_pipeline_off_builds_no_extender():
    g = _graph()
    e = InfluenceEngine(g, IMMConfig(k=K, batch=BATCH, max_theta=1024,
                                     fused_pipeline="off"))
    assert e._fused is None


# ------------------------------------------------------------- selection
#
# The fused chain hands selection a store; the one greedy loop answers
# over it, in every at-rest form, as the plain greedy does over the same
# rows.


def _assert_plain_greedy(sel, R, valid):
    """``sel`` is the plain greedy's answer over 0/1 rows ``R``: the
    same seeds, gains and covered_frac."""
    seeds, gains = _numpy_greedy(R, valid, K)
    np.testing.assert_array_equal(np.asarray(sel.seeds), seeds)
    np.testing.assert_array_equal(np.asarray(sel.gains), gains)
    assert float(sel.covered_frac) == float(
        np.float32(sum(gains)) / np.float32(max(valid.sum(), 1)))


def _rows(e):
    """The engine's one-chip arena as 0/1 rows, and its valid mask."""
    view = e.store.view()
    codec = getattr(e.store, "codec", None)
    R = np.asarray(view.R)
    return (R if codec is None else codec.decode_np(R)), np.asarray(
        view.valid)


@pytest.mark.parametrize("store", ["auto", "packed", "compressed"])
@pytest.mark.parametrize("method", ["rebuild", "decrement"])
def test_fused_selection_matches_baseline(store, method):
    g = _graph()
    e = InfluenceEngine(g, IMMConfig(k=K, batch=BATCH, max_theta=1024,
                                     seed=3, store=store))
    e.extend(THETA)
    _assert_plain_greedy(e.select(K, method=method), *_rows(e))


@pytest.mark.parametrize("method", ["rebuild", "decrement"])
def test_fused_selection_interpret(method):
    """cfg.pallas_interpret runs the packed arena's counts through the
    Pallas interpreter: still the plain greedy's answer."""
    g = _graph()
    e = InfluenceEngine(g, IMMConfig(k=K, batch=BATCH, max_theta=1024,
                                     seed=3, store="packed",
                                     pallas_interpret=True))
    e.extend(THETA)
    _assert_plain_greedy(e.select(K, method=method), *_rows(e))


def test_fused_selection_registry_complete():
    """Every layout `InfluenceEngine.select` can choose resolves, for
    both rules — including the sparse layouts the C4 adaptive switch
    needs."""
    for method in ("rebuild", "decrement"):
        for layout in ("dense", "packed", "compressed", "sharded",
                       "sparse", "sharded-sparse"):
            assert callable(get_selection(method, layout))


# ------------------------------------------------------------- meshed chain


needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >=4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4)")


@needs_mesh
@pytest.mark.parametrize("store", ["auto", "packed"])
@pytest.mark.parametrize("partition", ["equal", "balanced"])
def test_fused_matches_legacy_sharded(store, partition):
    from repro.configs.imm_snap import make_im_mesh, mesh_engine_kwargs
    mk = mesh_engine_kwargs(make_im_mesh("2x2"))
    off, on = _pair(store=store, mesh_kwargs=mk, partition=partition)
    _assert_bitwise(off, on)


@needs_mesh
def test_fused_sharded_matches_single_device():
    """The meshed fused chain reproduces the single-device stream —
    sharding is layout, never sampling semantics."""
    from repro.configs.imm_snap import make_im_mesh, mesh_engine_kwargs
    _, local = _pair()
    mk = mesh_engine_kwargs(make_im_mesh("2x2"))
    _, meshed = _pair(mesh_kwargs=mk)
    np.testing.assert_array_equal(np.asarray(local.store.counter),
                                  np.asarray(meshed.store.counter))
    s_l, s_m = local.select(K), meshed.select(K)
    np.testing.assert_array_equal(np.asarray(s_l.seeds),
                                  np.asarray(s_m.seeds))
    assert float(s_l.covered_frac) == float(s_m.covered_frac)


@needs_mesh
@pytest.mark.parametrize("method", ["rebuild", "decrement"])
def test_fused_selection_sharded(method):
    """The sharded strategy on the balanced 2x2 layout answers as the
    plain greedy does over the single-device engine's rows."""
    from repro.configs.imm_snap import make_im_mesh, mesh_engine_kwargs
    g = _graph()
    mk = mesh_engine_kwargs(make_im_mesh("2x2"))
    cfg = IMMConfig(k=K, batch=BATCH, max_theta=1024, seed=3,
                    partition="balanced")
    e, local = InfluenceEngine(g, cfg, **mk), InfluenceEngine(g, cfg)
    e.extend(THETA)
    local.extend(THETA)
    _assert_plain_greedy(e.select(K, method=method), *_rows(local))
