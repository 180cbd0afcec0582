"""InfluenceEngine — composable, resumable, multi-query IMM.

The monolithic ``imm(graph, cfg)`` call hid the paper's three tunable
subsystems (RRR storage C3/C4, counter update C5, theta scheduling) inside
one function that re-sampled from scratch per invocation.  This module
splits them apart around a stateful engine over a persistent `RRRStore`:

    engine = InfluenceEngine(graph, IMMConfig(model="IC"))
    result = engine.run()                 # Algorithm 1, exactly as before
    top10  = engine.select(10)            # more queries, NO re-sampling
    sigma  = engine.influence([5, 17])    # sigma(S) for any candidate set
    engine.snapshot(ckpt_dir)             # resumable via checkpoint.store

Pieces:
  * sampling is resolved through the sampler registry
    (``repro.core.sampler``): a ``DiffusionModel`` x ``TraversalBackend``
    composition — "IC/dense", "WC/sparse", "GT/pallas+stable",
    "LT/walk", ... via ``make_sampler`` — or any user-registered name
    (the legacy monolithic spellings still resolve, deprecated);
  * selection goes through the `SelectionStrategy` registry
    (``repro.core.selection.get_selection``: rebuild/decrement x
    dense/sparse/packed/compressed/sharded/sharded-sparse, every one the
    same greedy loop) instead of if/elif dispatch;
  * sampled sets land in a preallocated `RRRStore` arena (amortized
    doubling, in-place batch writes — see ``repro.core.store``), so
    ``extend``/``select`` never re-concatenate O(theta) rows; with a mesh
    the arena is a `ShardedStore` — the theta axis lives partitioned
    across devices end-to-end (paper C1), so theta scales with device
    count instead of single-device memory;
  * ``select`` results are memoized per (store version, k, method): a
    campaign sweep over many k is sampling-free after the first solve.

``imm()`` in ``repro.core.imm`` is a thin wrapper over ``run()`` and is
seed-for-seed identical to the historical implementation.
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.graphs.csr import Graph
from repro.core import martingale as mg
from repro.core.adaptive import choose_representation, l_pad_for
from repro.core.sampler import bind_sampler, default_sampler_name, get_sampler
from repro.core.selection import get_selection
import repro.core.pack  # noqa: F401 — registers IMPack stores/strategies
from repro.core.store import (
    RRRStore, ShardedStore, make_store, next_pow2, store_from_state,
)
from repro.checkpoint import store as ckpt
from repro.graphs.partition import resolve_partition

# the IMPack at-rest representations a cfg.store can name (beyond the
# legacy auto/bitmap/indices/sharded) — restore re-encodes into these
_PACK_REPS = ("packed", "compressed")


@dataclasses.dataclass
class IMMConfig:
    k: int = 50
    eps: float = 0.5
    ell: float = 1.0
    # diffusion model axis: "IC" | "LT" | "WC" | "GT" | any name passed to
    # repro.core.sampler.register_model
    model: str = "IC"
    # traversal backend axis: None = auto (dense below dense_sampler_max_n,
    # sparse above it; walk for walk-family models) | "dense" | "sparse" |
    # "pallas" (the fused MXU ic_frontier kernel; jnp oracle off-TPU) |
    # "walk" | any name passed to register_backend
    backend: Optional[str] = None
    # stability axis: identity-keyed counter-mode coins + positions
    # row-subset resampling (the delta-stable form streaming requires)
    stable: bool = False
    # force the Pallas ic_frontier kernel through the interpreter (CPU
    # kernel validation; default off-TPU dispatch uses the jnp oracle)
    pallas_interpret: bool = False
    batch: int = 256                  # RRR sets per sampling call
    max_theta: int = 1 << 16          # safety cap (config-controlled)
    dense_sampler_max_n: int = 4096   # use the MXU log-semiring sampler below
    # the greedy loop's counter update: "rebuild" (C5's adaptive update,
    # from the smaller of the newly covered and the surviving sets) or
    # "decrement" (the Ripples baseline: subtract the covered sets)
    selection_method: str = "rebuild"
    adaptive_representation: bool = True  # C4
    # below this n the dense bitmap wins regardless of coverage (the
    # mat-vec is MXU/cache-friendly and the bitmap->indices conversion
    # costs more than it saves — measured: LT replicas at n~4k ran 10x
    # slower through the index path; EXPERIMENTS §Paper-tables)
    sparse_rep_min_n: int = 65536
    switch_ratio: int = 32
    # "auto" resolves to "sharded" when the engine has a mesh, "bitmap"
    # otherwise; "sharded" demands a mesh.  "packed" (bit-packed, 8x
    # smaller at rest) and "compressed" (token lists, decode-and-count
    # reads) are the IMPack at-rest formats — on a mesh they resolve to a
    # ShardedStore whose tiles use that codec.  Representation never
    # changes results: all stores are seed-for-seed bitwise-identical
    store: str = "auto"   # "auto" | "bitmap" | "indices" | "packed"
    #                     # | "compressed" | "sharded"
    # vertex-axis column layout of a meshed store: "equal" keeps the
    # canonical contiguous equal blocks; "balanced" places the block
    # boundaries at the graph's dst-degree quantiles so per-shard edge
    # counts stay near-equal on power-law graphs (layout-only: seeds are
    # bitwise identical either way)
    partition: str = "equal"
    # double-buffer the 2D frontier all-gather behind the local logq
    # matmul (dense/pallas backends; ignored off-mesh).  Pure scheduling:
    # overlap on/off never changes a sampled set
    overlap: bool = True
    # fuse the sample->write->count chain into ONE jit per batch (the
    # (B, n) batch rows never rest as a separate device array — see
    # repro.core.fused): "auto" fuses whenever the store's at-rest form
    # supports it (bitmap/packed arenas, sharded bitmap/packed tiles),
    # "off" forces the historical two-call path.  Pure execution fusion:
    # the PRNG stream and every stored byte are bitwise-identical
    fused_pipeline: str = "auto"   # "auto" | "off"
    # full sampler-name override ("WC/pallas+stable", a legacy alias, or a
    # user registration); None = compose from (model, backend, stable)
    sampler: Optional[str] = None
    seed: int = 0


@dataclasses.dataclass
class IMMResult:
    seeds: np.ndarray
    influence: float          # n * covered_frac
    covered_frac: float
    theta: int
    rounds: int
    representation: str
    counter: np.ndarray       # fused global counter over all sampled sets


@dataclasses.dataclass(frozen=True)
class Selection:
    """One answered seed-selection query (no sampling state attached)."""
    seeds: np.ndarray
    covered_frac: float
    influence: float
    gains: np.ndarray
    representation: str
    theta: int                # store size the query was answered against


class InfluenceEngine:
    """Stateful IMM engine over a persistent RRR store.

    Parameters
    ----------
    graph, cfg : the problem and its knobs (see `IMMConfig`).
    store      : optional pre-built `RRRStore` (default: ``cfg.store``).
    mesh, theta_axes, vertex_axis : pass a mesh to run the paper's C1
        partitioning end-to-end — the engine then keeps its RRR arenas in
        a `ShardedStore` (theta axis partitioned over ``theta_axes``),
        samplers place their batches shard-local, and selection consumes
        the arena shards natively, psum-ing only reduced quantities.
        ``vertex_axis`` names a second mesh axis that shards the *vertex*
        dimension end-to-end: arena columns, sampler traversal tables,
        fused counter partials, and selection all hold only ``n / Dv``
        vertex columns per device, so theta scales with the theta axis
        and graph size with the vertex axis simultaneously (build the
        mesh with ``configs.imm_snap.make_im_mesh``).  Passing a
        pre-built `ShardedStore` implies its mesh and axes.

    A mesh-equipped engine is seed-for-seed identical to a single-device
    one for fixed ``cfg.seed`` — sharding changes layout, never results.
    """

    def __init__(self, graph: Graph, cfg: IMMConfig = None, *,
                 store: RRRStore = None, mesh=None,
                 theta_axes=("data",), vertex_axis=None):
        self.graph = graph
        self.cfg = cfg if cfg is not None else IMMConfig()
        if mesh is None and isinstance(store, ShardedStore):
            mesh, theta_axes = store.mesh, store.theta_axes
            vertex_axis = store.vertex_axis
        self.mesh = mesh
        self.theta_axes = tuple(theta_axes)
        self.vertex_axis = vertex_axis
        self.key = jax.random.PRNGKey(self.cfg.seed)
        if store is not None:
            self.store = store
        elif mesh is not None and self.cfg.store in ("auto", "sharded"):
            self.store = make_store(
                "sharded", graph.n, mesh=mesh, theta_axes=self.theta_axes,
                vertex_axis=vertex_axis,
                partition=self._resolve_partition(mesh, vertex_axis))
        elif mesh is not None and self.cfg.store in ("packed", "compressed"):
            # the IMPack at-rest formats shard like bitmaps — every tile
            # of the mesh arena is encoded with the configured codec
            self.store = make_store(
                "sharded", graph.n, mesh=mesh, theta_axes=self.theta_axes,
                vertex_axis=vertex_axis, codec=self.cfg.store,
                partition=self._resolve_partition(mesh, vertex_axis))
        elif mesh is not None and self.cfg.store == "indices":
            # fail fast: the sharded pipeline (store, selection, snapshot
            # restore) is dense-only, and the late failure used to surface
            # obscurely at the first select() or restore()
            raise ValueError(
                "store='indices' cannot be combined with a mesh: "
                "IndexStore (and its snapshots) is single-device only. "
                "Use a dense at-rest representation (store='auto', "
                "'bitmap', 'packed', or 'compressed'), all of which "
                "shard across the mesh.")
        elif self.cfg.store == "sharded":
            raise ValueError("store='sharded' needs a mesh")
        else:
            self.store = make_store(self.cfg.store, graph.n)
        self.sampler_name = self.cfg.sampler or default_sampler_name(
            graph, self.cfg)
        self._sample = bind_sampler(
            get_sampler(self.sampler_name), graph, self.cfg,
            placement=getattr(self.store, "batch_sharding", None))
        # C4 routed per-backend: when the arena is an IndexStore and the
        # bound sampler can emit index lists natively (the sparse
        # backend), batches flow sampler -> arena as lists — no (B, n)
        # bitmap densification and no bitmap_to_indices pass at the write
        self._reset_index_emission()
        self._rebind_fused()
        self._select_cache: dict = {}

    def _resolve_partition(self, mesh, vertex_axis):
        """The configured vertex-axis `VertexPartition` for a meshed
        store (None off-mesh/1D, where there is no vertex axis to lay
        out).  ``cfg.partition="balanced"`` derives the boundaries from
        the graph's dst degrees — deterministic per (graph, Dv), so
        replicas and restores rebuild the identical layout."""
        if mesh is None or vertex_axis is None:
            return None
        return resolve_partition(
            getattr(self.cfg, "partition", "equal"), self.graph.n,
            int(mesh.shape[vertex_axis]), dst=self.graph.edge_dst)

    def _reset_index_emission(self) -> None:
        """Recompute the native-emission width for the *current* store —
        zero (bitmap path) unless the store is an IndexStore and the
        bound sampler supports ``emit_l``.  Called at construction and
        after every store swap (restore is elastic across store kinds, so
        a stale width would route bitmap stores into the index path)."""
        self._emit_l = 0
        if (self.store.representation == "indices"
                and getattr(self._sample, "supports_index_emit", False)):
            self._emit_l = int(getattr(self.store, "l_pad", 4))

    def _rebind_fused(self) -> None:
        """(Re)build the fused sample->write->count extender for the
        current (store, bound sampler) pair — None when disabled or
        unsupported (index emission, IndexStore), in which case `extend`
        keeps the historical two-call path.  Called at construction and
        after every store swap or sampler rebind."""
        self._fused = None
        if getattr(self.cfg, "fused_pipeline", "auto") == "off" or self._emit_l:
            return
        from repro.core.fused import make_fused_extender
        self._fused = make_fused_extender(
            self.store, self._sample, self.cfg,
            sampler_name=self.sampler_name)

    # ------------------------------------------------------------ sampling

    @property
    def theta(self) -> int:
        return self.store.count

    def extend(self, theta: int) -> int:
        """Sample batches until the store holds >= ``theta`` RRR sets.

        Idempotent when the store is already large enough; returns the new
        store size.  The PRNG key stream is (key_i, sub_i) = split(key_{i-1})
        per batch — identical to the historical driver, so a fixed
        ``cfg.seed`` yields a bitwise-identical sample stream.  Under a
        `StorePressurePolicy` the target clamps to the store's row cap
        (the store evicts to make room, so the count would never pass it).
        """
        cap = getattr(self.store, "row_cap", None)
        target = theta if cap is None else min(theta, cap)
        batches = 0
        with obs.span("extend", tier="engine", target=target) as sp:
            while self.store.count < target:
                batches += 1
                self.key, sub = jax.random.split(self.key)
                if self._emit_l:
                    with obs.span("sample", tier="engine",
                                  sampler=self.sampler_name):
                        rows_idx, counter = self._sample_index_batch(sub)
                    self.store.add_index_batch(rows_idx, counter)
                elif (self._fused is not None
                        and self._fused.extend_once(sub)):
                    pass  # one fused jit did sample+write+count for sub
                else:
                    with obs.span("sample", tier="engine",
                                  sampler=self.sampler_name):
                        visited, counter, _ = self._sample(sub)
                    self.store.add_batch(visited, counter)
                obs.counter("engine.batches_sampled").add(1)
            if sp is not None:
                sp.set(batches=batches)
        obs.gauge("engine.theta").set(self.store.count)
        return self.store.count

    def _sample_index_batch(self, sub):
        """Draw one batch natively as index lists (C4 per-backend).  A
        row that comes back *full* may have been truncated at the
        emission width — double ``emit_l`` and re-emit with the same key
        (same coins, wider lists; bounded by O(log n) retries over the
        engine's lifetime, since the width only ever grows).  The width
        caps at ``n`` exactly (not the next power of two: the top_k
        inside the conversion cannot exceed the bitmap's minor dimension,
        and no set can hold more than n members)."""
        while True:
            rows_idx, counter, _ = self._sample(sub, emit_l=self._emit_l)
            if (self._emit_l >= self.graph.n
                    or not bool((rows_idx[:, -1] < self.graph.n).any())):
                return rows_idx, counter
            self._emit_l = min(self._emit_l * 2, self.graph.n)

    def sample_batch(self):
        """Advance the engine's PRNG stream by one batch without writing
        to the store: returns ``(batch_key, visited, counter)``.  The key
        chain is the same ``split`` sequence `extend` uses, so callers
        that record ``batch_key`` (streaming refresh) can later
        `resample` the identical batch."""
        self.key, sub = jax.random.split(self.key)
        visited, counter, _ = self._sample(sub)
        return np.asarray(sub), visited, counter

    @property
    def supports_row_resample(self) -> bool:
        """Whether the bound sampler can re-generate an arbitrary subset
        of a batch's rows (the stable samplers' ``positions`` hook)."""
        return "positions" in inspect.signature(self._sample).parameters

    def resample(self, batch_key, positions=None):
        """Re-run the sampler for a recorded batch key against the
        *current* graph: returns ``(visited, counter)``.  With a
        delta-stable sampler, rows whose traversal avoided all mutated
        vertices come back bitwise identical — the streaming repair path.
        ``positions`` (requires `supports_row_resample`) re-generates
        only those rows of the batch, so repair work is proportional to
        stale rows."""
        key = jnp.asarray(batch_key)
        if positions is None:
            visited, counter, _ = self._sample(key)
        else:
            visited, counter, _ = self._sample(
                key, positions=jnp.asarray(positions, jnp.int32))
        return visited, counter

    def rebind_graph(self, graph: Graph) -> None:
        """Point the engine at a mutated graph (streaming delta path):
        future sampling uses the new edges while the store's resident RRR
        sets are kept — `repro.stream` invalidates the stale ones.  The
        select memoization is NOT cleared here; stream consumers bump the
        store version (kill/replace) which keys the cache."""
        self.graph = graph
        self._sample = bind_sampler(
            get_sampler(self.sampler_name), graph, self.cfg,
            placement=getattr(self.store, "batch_sharding", None))
        self._rebind_fused()

    # ----------------------------------------------------------- selection

    def _choose_representation(self) -> str:
        """The C4 adaptive choice, generalized over at-rest formats: the
        answer is either ``"indices"`` (sparse sets past the switch
        ratio) or the store's own resident representation (``"bitmap"``
        / ``"packed"`` / ``"compressed"`` — the dense layouts all serve
        selection natively, so the store never converts except to the
        derived index view)."""
        rep = self.store.representation
        if rep == "indices":
            return "indices"
        cfg = self.cfg
        if cfg.adaptive_representation and self.graph.n >= cfg.sparse_rep_min_n:
            if isinstance(self.store, ShardedStore):
                # C4 per *vertex shard*: each shard's index lists hold
                # only its own n_local columns of every set, so both the
                # width threshold and the bitmap width it competes with
                # are local quantities — adding vertex shards makes the
                # index representation win earlier
                avg_cov, _ = self.store.coverage_stats()
                chosen = choose_representation(
                    avg_cov, self.store.n_local,
                    self.store.max_local_size(), cfg.switch_ratio)
            else:
                avg_cov, l_max = self.store.coverage_stats()
                chosen = choose_representation(
                    avg_cov, self.graph.n, l_max, cfg.switch_ratio)
            if chosen == "indices":
                return "indices"
        return rep

    def select(self, k: int = None, *, method: str = None) -> Selection:
        """Greedy max-coverage over the *current* store — re-queryable.

        Successive calls with the same (k, method) against an unchanged
        store return the memoized result; different k re-run only the
        selection kernel, never the sampler.
        """
        cfg = self.cfg
        k = min(cfg.k if k is None else int(k), self.graph.n)
        if k < 1:
            raise ValueError(f"select needs k >= 1, got {k}")
        method = method or cfg.selection_method
        cache_key = (self.store.version, self.store.count, k, method)
        hit = self._select_cache.get(cache_key)
        if hit is not None:
            obs.counter("engine.select_cache_hits").add(1)
            return hit
        obs.counter("engine.select_cache_misses").add(1)

        if self.mesh is not None:
            # a ShardedStore view hands its native arena tiles straight to
            # the strategy (no resharding — encoded packed/compressed
            # tiles decode inside the selection kernel through the
            # store's codec), a replicated BitmapStore view is scattered
            # on entry by shard_map.  The C4 adaptive choice runs here
            # too (per vertex shard): when sets are sparse enough,
            # selection consumes a tile-local index view through the
            # sharded-sparse strategy instead of the dense tiles
            if self.store.representation == "indices":
                raise ValueError(
                    "sharded selection requires a dense-at-rest store "
                    "(bitmap, packed, or compressed)")
            rep = self._choose_representation()
            if rep == "indices" and isinstance(self.store, ShardedStore):
                view = self.store.index_view(
                    l_pad_for(self.store.max_local_size()))
                layout = "sharded-sparse"
            else:
                rep = self.store.representation
                view, layout = self.store.view(), "sharded"
        else:
            rep = self._choose_representation()
            srep = self.store.representation
            if rep == "indices" and srep != "indices":
                _, l_max = self.store.coverage_stats()
                view = self.store.index_view(l_pad_for(l_max))
                layout = "sparse"
            else:
                view = self.store.view()
                layout = {"bitmap": "dense", "indices": "sparse",
                          "packed": "packed",
                          "compressed": "compressed"}[rep]
        strategy = get_selection(method, layout)
        with obs.span("select", tier="engine", k=k, method=method,
                      layout=layout) as sp:
            out = strategy(
                view, k, mesh=self.mesh, theta_axes=self.theta_axes,
                vertex_axis=self.vertex_axis,
                partition=getattr(self.store, "partition", None),
                codec=getattr(self.store, "codec", None),
                pallas_interpret=cfg.pallas_interpret)
            # strategies that count the arena rows they read report them
            rows = getattr(out, "rows", None)
            if sp is not None and rows is not None:
                sp.set(rows=rows, theta=int(view.R.shape[0]))
        seeds, frac, gains = jax.device_get(tuple(out))   # one transfer
        sel = Selection(
            seeds=np.asarray(seeds), covered_frac=float(frac),
            influence=float(frac) * self.graph.n, gains=np.asarray(gains),
            representation=rep, theta=self.store.count)
        self._select_cache[cache_key] = sel
        return sel

    # ----------------------------------------------------------- influence

    def influences(self, seed_sets: Sequence[Sequence[int]]) -> np.ndarray:
        """sigma(S) estimates for a batch of seed sets in one fused kernel.

        Sets may have different sizes; each is padded with its own first
        element (a no-op for coverage) and the query axis pads to a power
        of two, so recompilations stay bounded while any mix of campaign
        queries shares one store pass.
        """
        if not len(seed_sets):
            return np.zeros((0,), np.float64)
        sets = [np.asarray(s, np.int32).reshape(-1) for s in seed_sets]
        for i, s in enumerate(sets):
            if s.size == 0:
                raise ValueError(f"seed set {i} is empty")
            if (s < 0).any() or (s >= self.graph.n).any():
                raise ValueError(f"seed set {i} has out-of-range vertices")
        q = len(sets)
        l_pad = next_pow2(max(s.size for s in sets), 1)
        q_pad = next_pow2(q, 1)
        S = np.empty((q_pad, l_pad), np.int32)
        for i in range(q_pad):
            s = sets[min(i, q - 1)]
            S[i, :s.size] = s
            S[i, s.size:] = s[0]
        with obs.span("influence", tier="engine", queries=q):
            fracs = np.asarray(self.store.hits(S))[:q]
        return fracs.astype(np.float64) * self.graph.n

    def influence(self, seed_set: Sequence[int]) -> float:
        """sigma(S) ~= n * F_R(S) for one seed set against the store."""
        return float(self.influences([seed_set])[0])

    # ------------------------------------------------------- checkpointing

    def snapshot_tree(self) -> dict:
        """The engine's persistent state as a host pytree (store + PRNG
        key + meta) — `snapshot` saves exactly this; wrappers that keep
        state of their own (`repro.stream.StreamEngine`) embed it in a
        larger tree so one file restores the whole stack."""
        return {
            "store": self.store.state(),
            "key": np.asarray(self.key),
            "meta": {
                "n": np.int64(self.graph.n),
                "model": np.asarray(self.cfg.model),
                "sampler": np.asarray(self.sampler_name),
            },
        }

    def snapshot(self, directory: str, *, tag: str = "engine") -> str:
        """Persist store + PRNG state atomically (checkpoint.store format)."""
        return ckpt.save_named(directory, tag, self.snapshot_tree())

    def restore_tree(self, tree: dict) -> None:
        """Adopt a `snapshot_tree` (validates n/model, rebuilds the store
        elastically across layouts, resumes the PRNG stream)."""
        meta = tree["meta"]
        if int(meta["n"]) != self.graph.n:
            raise ValueError(
                f"snapshot is for n={int(meta['n'])}, graph has n={self.graph.n}")
        if str(np.asarray(meta["model"])) != self.cfg.model:
            raise ValueError(
                f"snapshot model {np.asarray(meta['model'])} != cfg.model "
                f"{self.cfg.model}")
        # elastic across layouts: a snapshot taken on any mesh (or none)
        # restores into this engine's *configured* store layout — sharded
        # engines reshard, engines that deliberately keep a replicated /
        # single-device store (cfg.store="bitmap" etc.) keep their kind
        mesh = self.mesh if isinstance(self.store, ShardedStore) else None
        vx = self.vertex_axis if mesh is not None else None
        # a packed/compressed-configured engine re-encodes whatever the
        # snapshot holds; legacy configs keep the snapshot's own kind
        target = (self.cfg.store if self.cfg.store in _PACK_REPS else None)
        self.store = store_from_state(
            tree["store"], mesh=mesh, theta_axes=self.theta_axes,
            vertex_axis=vx, partition=self._resolve_partition(mesh, vx),
            kind=target)
        self.key = jnp.asarray(tree["key"])
        self._reset_index_emission()
        self._rebind_fused()
        self._select_cache.clear()

    def restore(self, directory: str, *, tag: str = "engine") -> bool:
        """Resume from `snapshot`; returns False when none exists."""
        tree = ckpt.load_named(directory, tag)
        if tree is None:
            return False
        self.restore_tree(tree)
        return True

    def replicate(self, tree: dict = None) -> "InfluenceEngine":
        """A read replica of this engine: a new engine over the same
        graph/config/mesh whose store and PRNG state are restored from
        ``tree`` (default: a fresh ``snapshot_tree`` of this engine).

        The tree is deep-copied host-side first (`checkpoint.store.
        clone_tree`), so one snapshot fans out to any number of replicas
        none of which alias the primary's buffers — the primary keeps
        serving (and donating its arena on writes) while replicas answer
        ``select``/``influence`` queries bitwise-identically to the
        primary at the snapshot's store state.  Replicas restore through
        the same elastic path as `restore`, so a mesh-sharded primary
        fans out to mesh-sharded replicas."""
        if tree is None:
            tree = self.snapshot_tree()
        replica = InfluenceEngine(
            self.graph, self.cfg, mesh=self.mesh,
            theta_axes=self.theta_axes, vertex_axis=self.vertex_axis)
        replica.restore_tree(ckpt.clone_tree(tree))
        return replica

    # -------------------------------------------------- Algorithm 1 driver

    def run(self) -> IMMResult:
        """IMM Algorithm 1 (Sampling phase -> Set_Theta -> Selection).

        The martingale schedule gates `extend`; every intermediate coverage
        check reuses `select`'s memoization.  The store persists afterwards
        for further `select`/`influence` queries.
        """
        cfg, n = self.cfg, self.graph.n
        k = min(cfg.k, n)
        bounds = mg.compute_bounds(n, k, cfg.eps, cfg.ell)
        lb = 1.0
        rounds = 0

        with obs.span("run", tier="engine", n=n, k=k):
            for i in range(1, bounds.max_rounds + 1):
                rounds = i
                theta_i = min(mg.round_theta(bounds, i), cfg.max_theta)
                with obs.span("round", tier="engine", round=i,
                              theta=theta_i):
                    self.extend(theta_i)
                    sel = self.select(k)
                obs.counter("engine.rounds").add(1)
                if n * sel.covered_frac >= mg.round_target(bounds, i):
                    lb = mg.lower_bound_from_coverage(bounds, sel.covered_frac)
                    break
                if self.store.count >= cfg.max_theta:
                    lb = max(
                        mg.lower_bound_from_coverage(bounds, sel.covered_frac),
                        1.0)
                    break

            theta = min(mg.theta_from_lb(bounds, lb), cfg.max_theta)
            self.extend(theta)
            sel = self.select(k)
        return IMMResult(
            seeds=sel.seeds,
            influence=sel.influence,
            covered_frac=sel.covered_frac,
            theta=self.store.count,
            rounds=rounds,
            representation=sel.representation,
            counter=np.asarray(self.store.counter),
        )
