"""IMM experiment configs for the paper's 8 SNAP graphs (Table I / III).

Each entry pairs the SNAP graph stats with the paper's hyper-parameters
(k=50, eps=0.5) and the CPU-scale replica factor the benchmarks use.
``imm_dryrun_shapes`` defines the sharded-IMM cells the dry-run lowers
(theta x |V| bitmap selection + IC sampling steps on the production mesh).
``campaign_ks`` is the multi-query sweep a shared `InfluenceEngine` store
answers after one sampling pass (examples/influence_campaign.py and the
IMServer workload in launch/serve.py).

``make_im_mesh`` is the one mesh-configuration entry point every IM
driver shares (launch/im_run.py, launch/serve.py,
examples/influence_campaign.py, benchmarks/table3_runtime.py,
benchmarks/sharding_scaling.py): it maps a ``--mesh`` flag value — an
int/"auto" (1D theta sharding) or ``"RxC"`` (2D theta x vertex) — onto a
``jax.sharding.Mesh`` over ``THETA_AXIS``/``VERTEX_AXIS`` that the
`InfluenceEngine` uses to shard its RRR store (paper C1, both axes);
``mesh_engine_kwargs`` turns the mesh back into the engine's
``mesh``/``theta_axes``/``vertex_axis`` keywords so drivers stay
one-liners.  ``make_theta_mesh`` remains as the 1D-only spelling.
"""
from __future__ import annotations

import dataclasses

from repro.core.engine import IMMConfig
from repro.graphs.datasets import SNAP_STATS

# the mesh axis the RRR-set theta dimension shards over, everywhere — the
# ShardedStore, the sampler batch placement, and sharded selection all key
# off this name
THETA_AXIS = "data"
# the mesh axis the vertex dimension shards over on 2D meshes — arena
# columns, sampler traversal tables, counter partials, and selection all
# key off this name
VERTEX_AXIS = "vertex"


def make_theta_mesh(shards=None, *, axis: str = THETA_AXIS):
    """Resolve a ``--mesh`` flag into a theta-sharding mesh (or None).

    ``None``/``0`` -> no mesh: single-device engine, replicated
    `BitmapStore` (the sensible one-device default).  ``"auto"`` -> one
    theta shard per local device.  An int -> that many shards, clipped to
    the available device count so pod-sized flags degrade gracefully on a
    laptop (1 shard on 1 device — still the sharded code path, same
    results; sharding never changes results, only layout).  An
    already-built ``Mesh`` passes through unchanged, so programmatic
    callers need no flag-vs-mesh dispatch.
    """
    if shards in (None, 0, "0", "none"):
        return None
    if hasattr(shards, "shape"):        # already a Mesh
        return shards
    import jax
    from repro.launch.mesh import make_mesh

    avail = jax.device_count()
    n = avail if shards == "auto" else min(int(shards), avail)
    return make_mesh((n,), (axis,))


def make_im_mesh(spec=None, *, theta_axis: str = THETA_AXIS,
                 vertex_axis: str = VERTEX_AXIS):
    """Resolve a ``--mesh`` flag into a 1D *or* 2D influence mesh.

    Accepts everything `make_theta_mesh` does (None/0, int, ``"auto"``,
    a pre-built ``Mesh``) plus the 2D spellings ``"RxC"`` (e.g.
    ``"2x4"``: R theta shards x C vertex shards) and a ``(R, C)`` tuple.
    2D shapes clip to the available device count the same graceful way
    the 1D path does — the vertex axis shrinks first (theta sharding is
    the cheaper win: no frontier exchange), down to a 1-tile mesh on one
    device, which still runs the full 2D code path with identical
    results.
    """
    if spec in (None, 0, "0", "none"):
        return None
    if hasattr(spec, "shape"):          # already a Mesh
        return spec
    if isinstance(spec, str) and "x" in spec.lower():
        dt, dv = (int(p) for p in spec.lower().split("x", 1))
    elif isinstance(spec, (tuple, list)):
        dt, dv = int(spec[0]), int(spec[1])
    else:
        return make_theta_mesh(spec, axis=theta_axis)
    if dt < 1 or dv < 1:
        raise ValueError(f"mesh shape {dt}x{dv} must be >= 1x1")
    import jax
    from repro.launch.mesh import make_mesh

    avail = jax.device_count()
    dt = max(min(dt, avail), 1)             # theta sharding survives...
    dv = max(min(dv, avail // dt), 1)       # ...the vertex axis shrinks
    return make_mesh((dt, dv), (theta_axis, vertex_axis))


def mesh_engine_kwargs(mesh) -> dict:
    """`InfluenceEngine`/`StreamEngine` keyword arguments for a mesh from
    `make_im_mesh`: ``{}`` for None, otherwise ``mesh`` + ``theta_axes``
    (every axis that is not ``VERTEX_AXIS`` — so 1D meshes with custom
    axis names work too), plus ``vertex_axis`` when the mesh carries
    ``VERTEX_AXIS`` — drivers construct engines as ``Engine(g, cfg,
    **mesh_engine_kwargs(mesh))`` with no shape dispatch of their own."""
    if mesh is None:
        return {}
    names = tuple(mesh.axis_names)
    kw = {"mesh": mesh,
          "theta_axes": tuple(a for a in names if a != VERTEX_AXIS)}
    if VERTEX_AXIS in names:
        kw["vertex_axis"] = VERTEX_AXIS
    return kw

# seed-set sizes an influence campaign sweeps against one sampled store —
# the engine memoizes per-k selections, so the sweep costs one selection
# kernel per k and zero additional sampling
CAMPAIGN_KS = (5, 10, 20, 50)


@dataclasses.dataclass(frozen=True)
class IMMExperiment:
    graph: str
    n: int
    m: int
    directed: bool
    cfg_ic: IMMConfig
    cfg_lt: IMMConfig
    # the two scenario models the sampler decomposition shipped: weighted
    # cascade (1/indeg edge probs) and generalized triggering (the LT
    # weights as independent marginals) — both run every coin backend
    cfg_wc: IMMConfig
    cfg_gt: IMMConfig
    bench_scale: float        # CPU benchmark shrink factor
    campaign_ks: tuple = CAMPAIGN_KS


def _mk(graph: str, bench_scale: float) -> IMMExperiment:
    n, m, directed = SNAP_STATS[graph]
    return IMMExperiment(
        graph=graph, n=n, m=m, directed=directed,
        cfg_ic=IMMConfig(k=50, eps=0.5, model="IC"),
        cfg_lt=IMMConfig(k=50, eps=0.5, model="LT"),
        cfg_wc=IMMConfig(k=50, eps=0.5, model="WC"),
        cfg_gt=IMMConfig(k=50, eps=0.5, model="GT"),
        bench_scale=bench_scale,
    )


IMM_EXPERIMENTS = {
    "com-Amazon":  _mk("com-Amazon", 0.01),
    "com-YouTube": _mk("com-YouTube", 0.004),
    "com-DBLP":    _mk("com-DBLP", 0.01),
    "com-LJ":      _mk("com-LJ", 0.001),
    "soc-Pokec":   _mk("soc-Pokec", 0.002),
    "as-Skitter":  _mk("as-Skitter", 0.002),
    "web-Google":  _mk("web-Google", 0.004),
    "Twitter7":    _mk("Twitter7", 0.0001),
}


# Sharded-IMM dry-run cells: (theta, n) selection problems at production
# scale.  theta per the paper's regimes (IC ~1e4, LT ~1e8 is capped by the
# bitmap-memory budget — the adaptive representation handles LT's sparse
# sets; the dry-run lowers the dense path, which dominates compute).
IMM_DRYRUN_CELLS = {
    "imm_select_youtube_ic": {
        "n": 1_134_890, "theta": 16_384, "k": 50, "model": "IC",
        "note": "dense bitmap selection, com-YouTube scale"},
    "imm_select_lj_ic": {
        "n": 3_997_962, "theta": 8_192, "k": 50, "model": "IC",
        "note": "dense bitmap selection, com-LJ scale"},
    "imm_sample_google_ic": {
        "n": 875_713, "m": 5_105_039, "batch": 4_096, "bfs_steps": 16,
        "model": "IC", "note": "sparse frontier sampling, web-Google scale"},
}


# Sampler-matrix benchmark cells (benchmarks/sampler_matrix.py -> BENCH_4):
# the model x backend grid timed on one synthetic graph per size class.
# ``backends`` lists the traversal backends each coin model sweeps (the
# walk-family LT row runs the walk backend only); ``tiny`` is the CI
# smoke shape.
SAMPLER_MATRIX_CELLS = {
    "tiny":    {"n": 192, "m": 1024, "theta": 256, "batch": 128},
    "default": {"n": 1024, "m": 8192, "theta": 4096, "batch": 256},
}
SAMPLER_MATRIX_BACKENDS = ("dense", "sparse", "pallas")


# Multi-query serving cells: one resident engine store answering batched
# sigma(S) queries (the IMServer regime).  ``queries`` is the coalesced
# batch width, ``l_pad`` the padded seed-set length — together with the
# pow2 store capacity these fix the fused membership kernel's shapes.
IM_SERVE_CELLS = {
    "imm_serve_youtube_ic": {
        "n": 1_134_890, "theta": 16_384, "queries": 256, "l_pad": 64,
        "model": "IC", "note": "batched influence queries, com-YouTube scale"},
    "imm_serve_amazon_ic": {
        "n": 334_863, "theta": 16_384, "queries": 1_024, "l_pad": 16,
        "model": "IC", "note": "high-QPS small-set queries, com-Amazon scale"},
}
