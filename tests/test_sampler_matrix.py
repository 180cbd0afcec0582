"""DiffusionModel x TraversalBackend decomposition: legacy-name goldens
(seed-for-seed vs the pre-decomposition monolithic samplers), the
model x backend x stable equivalence matrix, WC/GT end-to-end, the Pallas
engine backend, pow2 edge padding, and the legacy deprecation contract.

Mesh-touching tests use however many devices the process has — 1 in a
plain run, 4 under scripts/ci.sh's forced-4-device pass.
"""
import dataclasses
import hashlib
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.imm_snap import make_im_mesh, mesh_engine_kwargs
from repro.core.engine import IMMConfig, InfluenceEngine
from repro.core.imm import imm
from repro.core import sampler as smp
from repro.core.sampler import (
    CoinModel, bind_sampler, composed_name, get_sampler, make_sampler,
    sampler_matrix, stable_variant,
)
from repro.graphs import rmat_graph
from repro.stream import StreamEngine, random_delta
from repro.launch.mesh import make_mesh


def theta_mesh():
    return make_mesh((jax.device_count(),), ("data",))


def golden_graph():
    return rmat_graph(96, 768, seed=2)


def sha(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


# Captured from the pre-decomposition monolithic samplers (commit
# f8d237a) on golden_graph() with batch=64, key=PRNGKey(123); ":positions"
# rows are the stable twins re-generating rows [5, 63, 17, 4].  Recorded
# under jax 0.9.0's default partitionable threefry
# (``jax_threefry_partitionable=True``) from commit 72ea3a9, whose stream
# the first capture matched under the older non-partitionable generator.
SAMPLER_GOLDENS = {
    "IC-dense": "7905103a2e8aeb65",
    "IC-sparse": "727990a4fd0dc2ba",
    "LT": "a747c88320ac8482",
    "IC-dense-stable": "9a488948025356c6",
    "IC-dense-stable:positions": "b6efcb30bcbbf93d",
    "IC-sparse-stable": "417867912b15e97a",
    "IC-sparse-stable:positions": "6b02b15c9f173747",
    "LT-stable": "4fa0ccc16d056948",
    "LT-stable:positions": "2bc443599d4023a4",
}

# imm() driver goldens on rmat_graph(192, 1536, seed=2) with
# IMMConfig(k=4, batch=128, max_theta=512, seed=7) — same provenance.
IMM_GOLDENS = {
    "IC": {"seeds": [83, 93, 118, 123], "theta": 512,
           "covered_frac": 0.61328125, "counter_sha": "40810693059d54ec"},
    "LT": {"seeds": [0, 16, 32, 8], "theta": 512,
           "covered_frac": 0.2265625, "counter_sha": "11fd2c84214f5880"},
    # IC forced through the sparse backend (dense_sampler_max_n=8)
    "IC-sparse": {"seeds": [83, 52, 93, 118], "theta": 512,
                  "covered_frac": 0.650390625,
                  "counter_sha": "65af4875cb056601"},
}

LEGACY_TO_AXES = {
    "IC-dense": ("IC", "dense", False),
    "IC-sparse": ("IC", "sparse", False),
    "LT": ("LT", "walk", False),
    "IC-dense-stable": ("IC", "dense", True),
    "IC-sparse-stable": ("IC", "sparse", True),
    "LT-stable": ("LT", "walk", True),
}


# ------------------------------------------------- seed-for-seed goldens ----

@pytest.mark.parametrize("name", sorted(LEGACY_TO_AXES))
def test_legacy_name_matches_pre_refactor_golden(name):
    """Every legacy registry name still emits the exact pre-decomposition
    sample stream (visited bitmaps, fused counter, roots)."""
    g = golden_graph()
    model, backend, stable = LEGACY_TO_AXES[name]
    cfg = IMMConfig(batch=64, model="LT" if model == "LT" else "IC",
                    sampler=name)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        fn = bind_sampler(get_sampler(name), g, cfg)
    v, c, r = fn(jax.random.PRNGKey(123))
    assert sha(v, c, r) == SAMPLER_GOLDENS[name]
    if stable:
        pos = jnp.asarray([5, 63, 17, 4], jnp.int32)
        v2, c2, r2 = fn(jax.random.PRNGKey(123), positions=pos)
        assert sha(v2, c2, r2) == SAMPLER_GOLDENS[name + ":positions"]


@pytest.mark.parametrize("name", sorted(LEGACY_TO_AXES))
def test_legacy_name_equals_make_sampler_composition(name):
    """Legacy aliases resolve through the composed axes: the alias, the
    canonical registry name, and a direct make_sampler() factory all
    produce bitwise-identical batches."""
    g = golden_graph()
    model, backend, stable = LEGACY_TO_AXES[name]
    cfg = IMMConfig(batch=64, model=model)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = bind_sampler(get_sampler(name), g, cfg)
    canonical = bind_sampler(
        get_sampler(composed_name(model, backend, stable)), g, cfg)
    composed = bind_sampler(make_sampler(model, backend, stable=stable),
                            g, cfg)
    key = jax.random.PRNGKey(123)
    outs = [f(key) for f in (legacy, canonical, composed)]
    for v, c, r in outs[1:]:
        np.testing.assert_array_equal(np.asarray(v), np.asarray(outs[0][0]))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(outs[0][1]))
        np.testing.assert_array_equal(np.asarray(r), np.asarray(outs[0][2]))


@pytest.mark.parametrize("case", sorted(IMM_GOLDENS))
def test_imm_driver_matches_pre_refactor_golden(case):
    """The end-to-end imm() driver (default dispatch through the new
    composition) reproduces the pre-decomposition seeds/theta/counter."""
    g = rmat_graph(192, 1536, seed=2)
    cfg = IMMConfig(k=4, model="LT" if case == "LT" else "IC", batch=128,
                    max_theta=512, seed=7)
    if case == "IC-sparse":
        cfg = IMMConfig(k=4, model="IC", batch=128, max_theta=512, seed=7,
                        dense_sampler_max_n=8)
    res = imm(g, cfg)
    want = IMM_GOLDENS[case]
    assert [int(s) for s in res.seeds] == want["seeds"]
    assert res.theta == want["theta"]
    assert res.covered_frac == pytest.approx(want["covered_frac"],
                                             rel=1e-12)
    assert sha(res.counter) == want["counter_sha"]


def test_goldens_hold_on_mesh():
    """The same golden stream lands from a mesh-sharded engine: sampling
    placement changes layout, never results (1 shard in a plain run, 4
    under the forced-4-device CI pass)."""
    g = rmat_graph(192, 1536, seed=2)
    cfg = IMMConfig(k=4, model="IC", batch=128, max_theta=512, seed=7)
    res = InfluenceEngine(g, cfg, mesh=theta_mesh()).run()
    want = IMM_GOLDENS["IC"]
    assert [int(s) for s in res.seeds] == want["seeds"]
    assert sha(res.counter) == want["counter_sha"]


# -------------------------------------------- model x backend x stable ----

COIN_CELLS = [(m, s) for m in ("IC", "WC", "GT") for s in (False, True)]


@pytest.mark.parametrize("model,stable", COIN_CELLS)
def test_dense_and_pallas_backends_agree_bitwise(model, stable):
    """The pallas backend is the dense math executed by the fused
    kernel: off-TPU dispatch (jnp oracle) and forced interpret-mode
    (the real kernel through the Pallas interpreter) are both bitwise
    equal to the dense backend for every coin model."""
    g = golden_graph()
    key = jax.random.PRNGKey(3)
    cfg = IMMConfig(batch=32, model=model)
    cfg_i = IMMConfig(batch=32, model=model, pallas_interpret=True)
    dense = bind_sampler(get_sampler(composed_name(model, "dense", stable)),
                         g, cfg)
    oracle = bind_sampler(get_sampler(composed_name(model, "pallas", stable)),
                          g, cfg)
    kernel = bind_sampler(get_sampler(composed_name(model, "pallas", stable)),
                          g, cfg_i)
    vd, cd, rd = dense(key)
    for fn in (oracle, kernel):
        v, c, r = fn(key)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(vd))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(cd))
        np.testing.assert_array_equal(np.asarray(r), np.asarray(rd))


@pytest.mark.parametrize("model,stable", COIN_CELLS)
def test_dense_and_sparse_backends_agree_in_distribution(model, stable):
    """Dense (log-semiring) and sparse (per-edge coin) execution of one
    model draw different coin layouts but the same distribution: mean
    RRR-set size agrees."""
    g = rmat_graph(128, 1024, seed=3)
    cfg = IMMConfig(batch=1024, model=model)
    d = bind_sampler(get_sampler(composed_name(model, "dense", stable)),
                     g, cfg)
    s = bind_sampler(get_sampler(composed_name(model, "sparse", stable)),
                     g, cfg)
    vd, _, _ = d(jax.random.PRNGKey(0))
    vs, _, _ = s(jax.random.PRNGKey(1))
    m_d = float(np.asarray(vd).sum(1).mean())
    m_s = float(np.asarray(vs).sum(1).mean())
    assert m_d == pytest.approx(m_s, rel=0.15), (m_d, m_s)


@pytest.mark.parametrize("model,backend", sampler_matrix())
def test_stable_cells_regenerate_row_subsets_exactly(model, backend):
    """positions=(...) re-generates exactly those rows for EVERY cell of
    the matrix — the property streaming repair is built on."""
    g = golden_graph()
    cfg = IMMConfig(batch=32, model=model)
    fn = bind_sampler(get_sampler(composed_name(model, backend, True)),
                      g, cfg)
    key = jax.random.PRNGKey(5)
    full, _, roots = fn(key)
    pos = np.asarray([3, 17, 4, 31])
    sub, _, sub_roots = fn(key, positions=jnp.asarray(pos, jnp.int32))
    np.testing.assert_array_equal(np.asarray(sub), np.asarray(full)[pos])
    np.testing.assert_array_equal(np.asarray(sub_roots),
                                  np.asarray(roots)[pos])


@pytest.mark.parametrize("model,backend", sampler_matrix())
def test_matrix_cell_mesh_equals_single_device(model, backend):
    """Every matrix cell is layout-independent end-to-end: a mesh-backed
    engine selects the same seeds as a single-device one (runs with 4
    real shards under scripts/ci.sh's forced-4-device pass)."""
    g = golden_graph()
    cfg = IMMConfig(k=3, batch=64, max_theta=128, seed=1, model=model,
                    backend=backend)
    local = InfluenceEngine(g, cfg)
    sharded = InfluenceEngine(g, cfg, mesh=theta_mesh())
    local.extend(128)
    sharded.extend(128)
    np.testing.assert_array_equal(np.asarray(local.store.counter),
                                  np.asarray(sharded.store.counter))
    a, b = local.select(3), sharded.select(3)
    np.testing.assert_array_equal(a.seeds, b.seeds)
    assert a.covered_frac == pytest.approx(b.covered_frac)


@pytest.mark.parametrize("model,backend", sampler_matrix())
def test_matrix_cell_2d_layouts_equal_single_device(model, backend):
    """Every matrix cell is invariant to the 2D vertex-column layout and
    the traversal schedule: edge-balanced blocks, overlap-off, and both
    at once all select bitwise the same seeds and counters as the
    single-device run (real theta x vertex tiles under scripts/ci.sh's
    forced-4-device pass — a 2x2 mesh there, 1x1 in a plain run)."""
    g = golden_graph()
    cfg = IMMConfig(k=3, batch=64, max_theta=128, seed=1, model=model,
                    backend=backend)
    local = InfluenceEngine(g, cfg)
    local.extend(128)
    ref_counter = np.asarray(local.store.counter)
    ref = local.select(3)
    d = jax.device_count()
    mesh = make_im_mesh((d // 2, 2) if d % 2 == 0 else (d, 1))
    kw = mesh_engine_kwargs(mesh)
    for variant in ({"partition": "balanced"}, {"overlap": False},
                    {"partition": "balanced", "overlap": False}):
        e = InfluenceEngine(g, dataclasses.replace(cfg, **variant), **kw)
        e.extend(128)
        np.testing.assert_array_equal(ref_counter,
                                      np.asarray(e.store.counter))
        sel = e.select(3)
        np.testing.assert_array_equal(ref.seeds, sel.seeds)
        assert ref.covered_frac == pytest.approx(sel.covered_frac)


def test_family_mismatch_fails_fast():
    with pytest.raises(ValueError, match="family"):
        make_sampler("LT", "dense")
    with pytest.raises(ValueError, match="family"):
        make_sampler("IC", "walk")
    # the config path fails with the same explanation, not a generic
    # unknown-sampler error at resolution time
    from repro.core.sampler import default_sampler_name
    with pytest.raises(ValueError, match="family"):
        default_sampler_name(golden_graph(),
                             IMMConfig(model="IC", backend="walk"))
    with pytest.raises(ValueError, match="unknown diffusion model"):
        make_sampler("SIR")
    with pytest.raises(ValueError, match="unknown traversal backend"):
        make_sampler("IC", "fpga")


def test_positional_cells_reject_positions():
    g = golden_graph()
    fn = bind_sampler(make_sampler("IC", "dense"), g, IMMConfig(batch=16))
    with pytest.raises(TypeError):
        fn(jax.random.PRNGKey(0), positions=jnp.asarray([0, 1], jnp.int32))


def test_post_import_model_resolves_through_config_path():
    """register_model alone is enough: the composed canonical names
    resolve on demand (engine config path, stable upgrade) with no
    explicit register_sampler calls."""
    from repro.core.sampler import register_model
    register_model(CoinModel("flat-post", lambda g: jnp.full(
        (g.m,), 0.1, jnp.float32)))
    g = golden_graph()
    engine = InfluenceEngine(
        g, IMMConfig(model="flat-post", k=2, batch=32, max_theta=64))
    assert engine.sampler_name == "flat-post/dense"
    engine.extend(64)
    assert len(engine.select(2).seeds) == 2
    assert stable_variant("flat-post/sparse") == "flat-post/sparse+stable"
    stream = StreamEngine(g, IMMConfig(model="flat-post", batch=32))
    assert stream.cfg.sampler == "flat-post/dense+stable"
    assert stream.engine.supports_row_resample
    with pytest.raises(ValueError, match="family"):
        get_sampler("flat-post/walk")


def test_register_model_shadowing_reaches_composed_samplers():
    """Re-registering a model name propagates to factories composed (or
    cached) before the re-registration — the documented overwrite
    contract — because names re-resolve at bind time."""
    from repro.core.sampler import register_model
    register_model(CoinModel("shadow-m", lambda g: jnp.zeros(
        (g.m,), jnp.float32)))                      # p=0: roots only
    g = golden_graph()
    cfg = IMMConfig(batch=32)
    fn = get_sampler("shadow-m/dense")              # composed + cached now
    v, _, _ = fn(g, cfg)(jax.random.PRNGKey(0))
    assert int(np.asarray(v).sum(1).max()) == 1     # only roots visited
    register_model(CoinModel("shadow-m", lambda g: jnp.ones(
        (g.m,), jnp.float32)))                      # shadow: p=1
    v2, _, _ = fn(g, cfg)(jax.random.PRNGKey(0))
    assert int(np.asarray(v2).sum(1).max()) > 1     # reachability kicks in


def test_custom_coin_model_runs_every_backend():
    """Adding a diffusion model is one edge_probs function; every coin
    backend (incl. Pallas) executes it with no further code."""
    flat = CoinModel("flat-0.05", lambda g: jnp.full((g.m,), 0.05,
                                                     jnp.float32))
    g = golden_graph()
    cfg = IMMConfig(batch=64)
    key = jax.random.PRNGKey(2)
    sizes = {}
    for backend in ("dense", "sparse", "pallas"):
        fn = bind_sampler(make_sampler(flat, backend), g, cfg)
        v, c, r = fn(key)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(v).sum(0))
        sizes[backend] = float(np.asarray(v).sum(1).mean())
    assert sizes["dense"] == sizes["pallas"]   # same math, same coins


# ------------------------------------------------- WC / GT end-to-end ----

@pytest.mark.parametrize("model", ["WC", "GT"])
def test_wc_gt_through_imm_and_engine(model):
    """The new models run the whole pipeline: imm() one-shot, then extra
    engine queries from the same store."""
    g = rmat_graph(192, 1536, seed=4)
    cfg = IMMConfig(k=4, model=model, batch=128, max_theta=512, seed=3)
    engine = InfluenceEngine(g, cfg)
    res = engine.run()
    assert len(set(int(s) for s in res.seeds)) == 4
    assert 0.0 < res.covered_frac <= 1.0
    assert res.influence == pytest.approx(res.covered_frac * g.n)
    sel = engine.select(2)
    np.testing.assert_array_equal(sel.seeds, res.seeds[:2])
    assert engine.influence(res.seeds) == pytest.approx(res.influence,
                                                        rel=1e-6)
    one_shot = imm(g, cfg)
    np.testing.assert_array_equal(one_shot.seeds, res.seeds)


@pytest.mark.parametrize("model", ["WC", "GT"])
def test_wc_gt_stream_refresh_equivalence(model):
    """The headline streaming invariant holds for the new models' stable
    forms: refresh-until-consistent == a fresh engine on the post-delta
    graph, seed-for-seed."""
    cfg = IMMConfig(k=4, batch=64, max_theta=512, seed=11, model=model)
    stream = StreamEngine(golden_graph(), cfg)
    assert stream.cfg.sampler == f"{model}/dense+stable"
    assert stream.engine.supports_row_resample
    stream.extend(256)
    rng = np.random.default_rng(21)
    for _ in range(2):
        stream.apply_delta(random_delta(
            stream.graph, rng, inserts=3, deletes=3, reweights=2))
    assert stream.refresh() == 0 and stream.consistent
    fresh = InfluenceEngine(stream.graph, stream.cfg)
    fresh.extend(stream.theta)
    a, b = stream.select(4), fresh.select(4)
    np.testing.assert_array_equal(a.seeds, b.seeds)
    np.testing.assert_array_equal(np.asarray(stream.store.counter),
                                  np.asarray(fresh.store.counter))


# -------------------------------------------------- the pallas backend ----

def test_pallas_backend_selectable_from_engine_config():
    """IMMConfig(backend='pallas') (the --sampler/--backend CLI path)
    drives kernels/ic_frontier.py through the engine and matches the
    dense backend's results exactly (off-TPU: ops.py oracle dispatch;
    pallas_interpret=True: the real kernel, interpreted)."""
    g = golden_graph()
    base = dict(k=3, batch=64, max_theta=256, seed=5)
    dense = InfluenceEngine(g, IMMConfig(backend="dense", **base))
    via_backend = InfluenceEngine(g, IMMConfig(backend="pallas", **base))
    via_name = InfluenceEngine(g, IMMConfig(sampler="IC/pallas", **base))
    interp = InfluenceEngine(g, IMMConfig(backend="pallas",
                                          pallas_interpret=True, **base))
    assert via_backend.sampler_name == via_name.sampler_name == "IC/pallas"
    results = {}
    for tag, e in (("dense", dense), ("backend", via_backend),
                   ("name", via_name), ("interp", interp)):
        e.extend(256)
        results[tag] = (np.asarray(e.store.counter), e.select(3).seeds)
    for tag in ("backend", "name", "interp"):
        np.testing.assert_array_equal(results[tag][0], results["dense"][0])
        np.testing.assert_array_equal(results[tag][1], results["dense"][1])


# ------------------------------------------- pow2 sparse edge padding ----

def test_stable_sparse_pads_edges_to_pow2_and_stays_bitwise():
    """The stable sparse backend pads its edge arrays to the next power
    of two (one jit trace per bucket, so a GraphDelta changing m inside
    the bucket never retraces) and padding is bitwise-invisible."""
    g = golden_graph()                       # m = 768 -> pads to 1024
    cfg = IMMConfig(batch=32)
    fn = bind_sampler(make_sampler("IC", "sparse", stable=True), g, cfg)
    key = jax.random.PRNGKey(9)
    v, c, r = fn(key)
    # the unpadded loop (direct call) produces the identical stream
    v0, c0, r0 = smp._sparse_loop(
        key, g.edge_src, g.edge_dst, g.in_prob, n_nodes=g.n, batch=32,
        stable=True)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v0))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r0))


def test_stable_sparse_trace_width_shared_across_deltas():
    """Graphs whose edge counts fall in one pow2 bucket bind stable
    sparse samplers with identical static shapes — the compiled kernel
    is reused instead of retraced per delta."""
    g = golden_graph()
    stream = StreamEngine(g, IMMConfig(batch=32, seed=0,
                                       sampler="IC/sparse+stable"))
    widths = set()
    rng = np.random.default_rng(31)
    for _ in range(3):
        # the bound sampler closes over the padded arrays; peek by name
        bound = stream.engine._sample
        free = dict(zip(bound.__code__.co_freevars, bound.__closure__))
        widths.add(int(free["src"].cell_contents.shape[0]))
        stream.apply_delta(random_delta(stream.graph, rng, inserts=2,
                                        deletes=1))
    assert len(widths) == 1 and widths.pop() == 1024


# -------------------------------------------------- legacy deprecation ----

def test_legacy_names_warn_once_each():
    smp._LEGACY_WARNED.discard("IC-dense")
    with pytest.warns(DeprecationWarning, match="make_sampler"):
        get_sampler("IC-dense")
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        get_sampler("IC-dense")              # second resolve: silent
    # canonical names never warn
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        get_sampler("IC/dense")
        get_sampler("WC/pallas+stable")


def test_stable_variant_spellings():
    assert stable_variant("IC/dense") == "IC/dense+stable"
    assert stable_variant("LT/walk+stable") == "LT/walk+stable"
    assert stable_variant("IC-sparse") == "IC-sparse-stable"
    assert stable_variant("LT-stable") == "LT-stable"
    assert stable_variant("my-custom-sampler") == "my-custom-sampler"
