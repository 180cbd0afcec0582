"""Batched RRR-set samplers (Generate_RRRsets, paper Alg. 3) — composed
from two orthogonal axes instead of a monolithic per-name fork.

A *sampler* answers "draw a batch of reverse-reachable sets"; historically
the registry hard-forked every answer six ways (``IC-dense``,
``IC-sparse``, ``LT`` and their ``-stable`` twins), so each new diffusion
model or execution scheme multiplied the fork count.  This module factors
the fork matrix into the axes that actually vary (the EFFICIENTIMM
observation — and the fused-IM-kernel result of Gökturk & Kaya,
arXiv:2008.03095 — that activation semantics generalize across cascade
models once they are separated from the traversal loop):

  * **DiffusionModel** — *what* the diffusion semantics are.  Two
    families:

      - `CoinModel` ("coins"): edge-factored semantics — each in-edge
        ``u -> v`` is consulted at most once (when ``v`` first enters the
        reverse frontier) and fires an independent Bernoulli coin with a
        model-supplied marginal.  This is Kempe et al.'s triggering model
        restricted to independent inclusion; built-ins: ``IC`` (the
        graph's per-edge probabilities), ``WC`` (weighted cascade,
        ``1/indeg(dst)``), and ``GT`` (generalized triggering with the
        graph's LT triggering weights as independent marginals).
      - `WalkModel` ("walk"): pick-at-most-one semantics — the vertex the
        walk sits at selects a single in-neighbor by weight (or none).
        Built-in: ``LT``.

  * **TraversalBackend** — *how* the traversal executes:

      - ``dense``  — probabilistic reverse BFS as a *log-semiring
        mat-vec* on the dense activation matrix: P(u activated by
        frontier F) = 1 - prod_{v in F} (1 - p_{u->v-reversed}); exact in
        distribution for reachability (DESIGN §2). MXU-friendly.
      - ``sparse`` — per-edge Bernoulli coins, each vertex pulling the
        OR of its out-edges' live heads over the source-sorted edge
        list; exact live-edge semantics, scales to graphs where the
        dense matrix does not fit.
      - ``pallas`` — the dense formulation with the frontier step
        executed by the fused Pallas MXU kernel
        ``kernels/ic_frontier.py`` (matmul + Bernoulli sampling + visited
        mask in one VMEM-resident pass).  Dispatch goes through
        ``repro.kernels.ops.ic_frontier_step``: the kernel on TPU, the
        jnp oracle elsewhere — numerically the *same math* as ``dense``,
        so results are bitwise identical off-TPU and on any
        single-k-tile problem.
      - ``walk``   — the random-walk loop (binary search over per-dst
        cumulative weights, CSC layout) for "walk"-family models.

  * **stable** — an orthogonal *flag*, not a source fork: positional
    coins (``uniform(key, shape)`` — fast, but any shape change renumbers
    every coin) vs identity-keyed counter-mode coins (hash of (step key,
    row position, edge/vertex id) — delta-stable and row-subsettable via
    ``positions``, the form streaming refresh requires).

``make_sampler(model, backend, stable=...)`` composes the axes into a
registry-compatible factory; the full matrix is pre-registered under
canonical ``"<model>/<backend>[+stable]"`` names (e.g. ``"WC/sparse"``,
``"IC/pallas+stable"``).  The historical monolithic names resolve as
deprecated aliases that are **seed-for-seed identical** to the
pre-decomposition samplers (goldens pinned in
tests/test_sampler_matrix.py).

Every bound sampler returns the batch as **visited bitmaps** ``(B, n)
uint8`` plus the fused in-place counter contribution (paper C3) and the
batch roots (the sparse backend can alternatively emit index lists
natively — C4 routed per-backend, see ``emit_l``).  Factories accept an
optional ``placement`` (a ``jax.sharding.NamedSharding`` for the
``(B, n)`` output — a `ShardedStore` hands out its ``batch_sharding``):
the generation loop is partitioned over the batch axis — and, when the
placement is 2D (``P(theta_axes, vertex_axis)``), over the vertex axis
too: each device samples exactly the (row block, vertex block) tile its
arena shard will store (paper C1, both axes).  The dense backends pin
their ``logq`` matrix to the same vertex blocks (`_shard_cols`), and the
sparse loop runs per device over a window of its edges
(`_sparse_loop`), so each device expands only its own vertex block from
the all-gathered frontier — the frontier exchange is the only
cross-shard traffic in the loop.
PRNG values are position- or identity-keyed, so placement changes layout
only — sampled sets are bitwise identical on any mesh shape.
"""
from __future__ import annotations

import dataclasses
import inspect
import warnings
from functools import partial
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.extend.random import threefry2x32_p
from jax.sharding import NamedSharding, PartitionSpec

from repro.compat import shard_map
from repro.core.adaptive import bitmap_to_indices
from repro.core.store import next_pow2
from repro.graphs.csr import Graph, dense_ic_matrix, edge_arrays, wc_edge_probs
from repro.kernels import ops as kops

_LOGQ_CLAMP = -30.0  # exp(-30) ~ 1e-13: treat p=1 edges as prob 1-1e-13


# ------------------------------------------- vertex-partitioned tables ----
#
# With a 2D batch placement (``P(theta_axes, vertex_axis)``, handed out by
# a 2D `ShardedStore`), the traversal state is column-partitioned over the
# vertex axis — so the graph tables the frontier step reads should be too,
# or every step would re-broadcast them.  ``_shard_cols`` pins a table's
# trailing axis to the placement's vertex axis (the same contiguous block
# layout as ``repro.graphs.partition.vertex_partition``, which GSPMD uses
# for trailing-dim shardings): the dense ``logq`` matrix becomes
# column-blocked, so each device computes activations only for its own
# vertex block from the all-gathered frontier — the frontier exchange is
# the only cross-shard traffic in the loop.  PRNG values are
# position- or identity-keyed, so all of this changes layout only: the
# sampled sets stay bitwise identical on any mesh shape.

def _vertex_axis_of(placement):
    """The vertex (column) mesh axis of a 2D batch placement, or None."""
    if placement is None:
        return None
    spec = tuple(placement.spec)
    return spec[1] if len(spec) > 1 else None


def _shard_cols(x, placement):
    """Constrain a graph table's trailing axis to the placement's vertex
    axis (no-op for 1D/absent placements): ``(n, n)`` tables become
    column-blocked."""
    vx = _vertex_axis_of(placement)
    if vx is None:
        return x
    spec = PartitionSpec(*((None,) * (x.ndim - 1) + (vx,)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(placement.mesh, spec))


# ---------------------------------------------------------------- models ----
#
# A DiffusionModel owns *semantics only*: how an edge (or a visited
# vertex's in-segment) turns randomness into activation.  It supplies the
# per-edge tables a backend consumes; it never owns a traversal loop, so
# adding a model is ~5 lines (see docs/samplers.md) and every compatible
# backend — including the Pallas kernel — works with it immediately.

@dataclasses.dataclass(frozen=True)
class CoinModel:
    """Edge-factored ("coins" family) diffusion semantics.

    ``edge_probs(graph) -> (m,) float32`` returns the CSC-order marginal
    activation probability of each in-edge.  Each edge is consulted at
    most once per RRR traversal — when its destination first enters the
    reverse frontier — and fires independently, which is exactly the
    triggering model with independent inclusion (IC is the instance whose
    marginals are the graph's edge probabilities).
    """
    name: str
    edge_probs: Callable[[Graph], jnp.ndarray]
    family: str = dataclasses.field(default="coins", init=False)


@dataclasses.dataclass(frozen=True)
class WalkModel:
    """Pick-at-most-one ("walk" family) diffusion semantics.

    ``walk_tables(graph) -> (dst_offsets, in_src, cum, total)`` returns
    the CSC segment offsets, in-neighbor ids, within-segment cumulative
    pick weights, and per-vertex total pick probability: one uniform draw
    ``r`` selects the in-neighbor whose cumulative interval contains it
    (or none when ``r >= total``), the Tang'15 LT RRR random walk.
    """
    name: str
    walk_tables: Callable[[Graph], tuple]
    family: str = dataclasses.field(default="walk", init=False)


def _wc_probs(graph: Graph) -> jnp.ndarray:
    """Weighted cascade: p(u -> v) = 1 / indeg(v) (CSC edge order; the
    formula lives in `repro.graphs.csr.wc_edge_probs`)."""
    return jnp.asarray(wc_edge_probs(graph.edge_dst, graph.n), jnp.float32)


def _gt_probs(graph: Graph) -> jnp.ndarray:
    """Generalized triggering: the graph's LT triggering weights as
    *independent* per-edge marginals (CSC order).

    LT and GT share the same per-edge marginals but sit at opposite
    correlation extremes of the triggering framework: LT's triggering set
    includes at most one in-neighbor (mutually exclusive picks), GT's
    includes each in-neighbor independently.  Per-dst LT weights sum to
    <= 1, so every marginal is a valid probability.
    """
    _, _, _, w = edge_arrays(graph)
    return jnp.asarray(np.clip(w, 0.0, 1.0), jnp.float32)


IC = CoinModel("IC", lambda g: g.in_prob)
WC = CoinModel("WC", _wc_probs)
GT = CoinModel("GT", _gt_probs)
LT = WalkModel("LT", lambda g: (g.dst_offsets, g.in_src, g.in_lt_cum,
                                g.in_lt_total))

_MODEL_REGISTRY: dict = {}


def register_model(model) -> None:
    """Register a `CoinModel`/`WalkModel` under its name (overwrites
    silently so experiments can shadow the built-ins).  Registered coin
    models compose with every frontier backend; walk models with the
    walk backend."""
    _MODEL_REGISTRY[model.name] = model


def get_model(name: str):
    try:
        return _MODEL_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown diffusion model {name!r}; registered: "
            f"{sorted(_MODEL_REGISTRY)}")


def registered_models():
    return sorted(_MODEL_REGISTRY)


for _m in (IC, WC, GT, LT):
    register_model(_m)


def logq_from_probs(graph: Graph, probs) -> jnp.ndarray:
    """Dense (n, n) log(1-p) matrix in *reverse-traversal* orientation
    for any per-edge marginal vector: logq[v, u] = log(1 - p_{u->v}) so
    that ``frontier @ logq`` accumulates over frontier nodes v the
    log-survival of u w.r.t. its out-edges into v."""
    P = dense_ic_matrix(graph, probs)
    return jnp.maximum(jnp.log1p(-P.T), _LOGQ_CLAMP)


def make_logq(graph: Graph) -> jnp.ndarray:
    """`logq_from_probs` for the IC model (the historical entry point)."""
    return logq_from_probs(graph, graph.in_prob)


# ------------------------------------------------ the stable-coin machinery ----
#
# The positional loops draw their randomness by *array position*
# (``uniform(key, shape)``): fast, but any change to the edge count
# renumbers every coin, and a batch can only ever be re-generated whole.
# With ``stable=True`` every coin is re-keyed by **identity** — a
# stateless counter-mode hash of (step key, row position, edge/vertex id)
# — which buys the two properties streaming (``repro.stream``) needs:
#
#   * **delta stability**: re-sampling a row with the same key on a
#     mutated graph reproduces it bitwise unless its traversal actually
#     touched a mutated edge's destination — exactly the staleness
#     predicate ``repro.stream.invalidate`` marks;
#   * **row-granular repair**: ``positions`` selects an arbitrary subset
#     of the batch's rows and re-generates *only those* (same coins the
#     full batch would have drawn), so refresh work is proportional to
#     stale rows, not to the batches they happen to live in.
#
# Distribution-wise each coin is still an independent-in-practice uniform;
# only the key-stream mechanism differs, so the stable twins are not
# coin-for-coin identical to their positional twins (they are separate
# registry entries and leave the historical ``imm()`` streams untouched).

def _mix32(x):
    """splitmix-style avalanche on uint32 (stateless counter-mode hash)."""
    x = (x ^ (x >> jnp.uint32(16))) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> jnp.uint32(15))) * jnp.uint32(0x846CA68B)
    return x ^ (x >> jnp.uint32(16))


def _u01(bits):
    """uint32 hash bits -> f32 uniform in [0, 1)."""
    return ((bits >> jnp.uint32(8)).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


_GOLD = 0x9E3779B9   # 2**32 / phi — the classic Weyl increment


# ---------------------------------------------- counter-mode positional coins ----
#
# ``jax.random.uniform(s, shape)`` under JAX's partitionable threefry
# (the default since JAX 0.5) is a pure function of each element's flat
# index: element ``i`` hashes the 64-bit counter ``i`` with
# ``threefry2x32(s, (hi, lo) of i)``, XORs the two output words, and keeps
# the top 23 bits as a float in [0, 1).  Drawing the same counters in
# another layout therefore gives the same coins bit for bit, which is what
# lets the sparse loop hold its coins edge-major without renumbering one.

def _counter_base(batch: int, m: int):
    """``(hi, lo)`` uint32 words of each row's first counter ``b * m``,
    ``(batch,)`` each, split exactly on the host."""
    base = np.arange(batch, dtype=np.uint64) * np.uint64(m)
    return ((base >> np.uint64(32)).astype(np.uint32),
            (base & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _counter_words(hi_b, lo_b, pos):
    """``(hi, lo)`` words of the 64-bit counters ``base[b] + pos[j]``,
    each ``(len(pos), len(base))``: ``pos`` carries into ``hi`` where
    ``lo`` wraps.  With `_counter_base`'s words that is element ``(j,
    b)`` of ``(batch, m)``'s flat index ``b * m + pos[j]``."""
    lo_b = jnp.asarray(lo_b)[None, :]
    lo = lo_b + pos.astype(jnp.uint32)[:, None]
    hi = jnp.asarray(hi_b)[None, :] + (lo < lo_b).astype(jnp.uint32)
    return hi, lo


def _uniform_at(key, hi, lo):
    """The elements of ``jax.random.uniform(key, shape)`` whose flat
    indices have the 64-bit words ``(hi, lo)``, in their layout."""
    kd = jnp.asarray(key, jnp.uint32).reshape(-1)
    x0, x1 = threefry2x32_p.bind(kd[0], kd[1], hi, lo)
    bits = (x0 ^ x1) >> jnp.uint32(9) | jnp.uint32(0x3F800000)
    return (jax.lax.bitcast_convert_type(bits, jnp.float32)
            - jnp.float32(1.0))


def _setup(key, batch, n_nodes, positions, placement, stable):
    """Shared traversal preamble: the (kroot, kstep) split, full-batch
    roots, initial visited state, and (stable only) per-row hash lanes.

    The PRNG op sequence is identical for both stability modes — one
    ``split`` plus one ``randint`` — so the root stream of a composed
    sampler matches the historical monolithic samplers bitwise.
    ``positions`` (stable only) gathers a row subset of the full batch.
    """
    kroot, kstep = jax.random.split(key)
    roots_full = jax.random.randint(kroot, (batch,), 0, n_nodes)
    if not stable:
        if positions is not None:
            raise ValueError(
                "positions-subset resampling needs stable=True "
                "(identity-keyed coins); positional samplers can only "
                "re-generate whole batches")
        roots = roots_full
        visited0 = jax.nn.one_hot(roots, n_nodes, dtype=jnp.bool_)
        if placement is not None:
            visited0 = jax.lax.with_sharding_constraint(visited0, placement)
        return kstep, roots, visited0, None
    pos = (jnp.arange(batch, dtype=jnp.int32) if positions is None
           else jnp.asarray(positions, jnp.int32))
    roots = roots_full[pos]
    visited0 = jax.nn.one_hot(roots, n_nodes, dtype=jnp.bool_)
    if placement is not None and positions is None:
        visited0 = jax.lax.with_sharding_constraint(visited0, placement)
    bb = pos.astype(jnp.uint32)[:, None] * jnp.uint32(_GOLD)
    return kstep, roots, visited0, bb


# --------------------------------------------------------- traversal loops ----
#
# One loop per backend family, written once.  ``stable`` selects the coin
# source; the PRNG split chain (one ``split`` per step) is shared, so the
# positional path reproduces the historical samplers bitwise and the
# stable path reproduces the historical ``-stable`` twins bitwise.

@partial(jax.jit, static_argnames=("batch", "max_steps", "stable", "kernel",
                                   "interpret", "placement", "overlap",
                                   "with_steps"))
def _dense_loop(key, logq, positions=None, *, batch: int, max_steps: int = 0,
                stable: bool = False, kernel: bool = False,
                interpret: bool = False, placement=None,
                overlap: bool = False, with_steps: bool = False):
    """Dense log-semiring frontier expansion (the ``dense`` and
    ``pallas`` backends; ``kernel=True`` routes the step through
    ``kernels.ops.ic_frontier_step`` — same math, fused on the MXU).

    ``overlap=True`` (2D placements only; a no-op otherwise) double-
    buffers the loop's one collective: the while-loop state carries the
    *vertex-axis-gathered* frontier, so the all-gather that step ``t+1``
    needs is issued at the end of step ``t``'s body — as soon as ``new``
    exists and *decoupled from the step-t matmul*, letting XLA's
    latency-hiding scheduler run the collective behind the local logq
    compute instead of serializing gather -> matmul inside one dot
    lowering.  A pure scheduling change: the gathered operand feeds the
    same full-width local matmul GSPMD lowers for the annotation-free
    path, so sampled sets are bitwise identical with overlap on or off.

    Returns ``(visited (K, n) uint8, counter (n,) int32, roots (K,))``
    where ``K = len(positions)`` (the full batch when ``positions`` is
    None; positional mode requires ``positions is None``), and with
    ``with_steps`` the while loop's trip count (int32) fourth.
    """
    n = logq.shape[0]
    max_steps = max_steps or n
    # 2D placement: column-block the activation matrix over the vertex
    # axis once, outside the loop — each device then owns the logq
    # columns of its own vertex block, and the per-step mat-vec needs
    # only the all-gathered frontier (the frontier exchange)
    logq = _shard_cols(logq, placement)
    kstep, roots, visited0, bb = _setup(
        key, batch, n, positions, placement, stable)
    uids = jnp.arange(n, dtype=jnp.uint32)[None, :] if stable else None
    overlap = overlap and _vertex_axis_of(placement) is not None
    if overlap:
        spec = tuple(placement.spec)
        gathered_sh = NamedSharding(placement.mesh,
                                    PartitionSpec(spec[0], None))

    def gather(x):
        """Issue the vertex-axis frontier all-gather (overlap mode)."""
        return (jax.lax.with_sharding_constraint(x, gathered_sh)
                if overlap else x)

    def cond(state):
        step, frontier, visited, _ = state
        return jnp.logical_and(step < max_steps, frontier.any())

    def body(state):
        step, frontier, visited, k = state
        k, sub = jax.random.split(k)
        if stable:
            kd = jnp.asarray(sub, jnp.uint32).reshape(-1)
            coin = _u01(_mix32(_mix32(uids ^ kd[0]) ^ bb ^ kd[1]))
        else:
            coin = jax.random.uniform(sub, frontier.shape)
        if kernel:
            new = kops.ic_frontier_step(
                frontier, visited, logq, coin,
                interpret=interpret).astype(jnp.bool_)
        else:
            acc = frontier.astype(jnp.float32) @ logq   # (K, n) log-survival
            p_act = -jnp.expm1(acc)                     # 1 - exp(acc)
            new = jnp.logical_and(coin < p_act, ~visited)
        # overlap: kick off step-(t+1)'s frontier collective here, while
        # nothing downstream in this body depends on the gathered copy
        return step + 1, gather(new), jnp.logical_or(visited, new), k

    steps, _, visited, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), gather(visited0), visited0, kstep)
    )
    counter = visited.sum(axis=0, dtype=jnp.int32)      # fused count (C3)
    out = (visited.astype(jnp.uint8), counter, roots)
    return out + (steps,) if with_steps else out


def _mesh_axes(placement, positions):
    """``(theta axes, vertex axis)`` the sparse loop splits its lanes and
    its vertices over: those of a ``(B, n)`` batch placement, or None
    each when there is none (or a ``positions`` subset, whose rows keep
    no placement)."""
    if placement is None or positions is not None:
        return None, None
    return tuple(placement.spec)[0], _vertex_axis_of(placement)


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    axes = axes if isinstance(axes, tuple) else (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _sparse_slab(edge_src, n_nodes: int, placement) -> int:
    """The CSR edges each device of the sparse loop walks under
    ``placement``: the most out-edges any block of its vertex axis has
    (every edge without a vertex axis)."""
    src = np.asarray(edge_src)
    vx = _vertex_axis_of(placement)
    if vx is None:
        return int(src.shape[0])
    dv = _axis_size(placement.mesh, vx)
    return int(np.bincount(src // -(-n_nodes // dv), minlength=dv).max())


@partial(jax.jit, static_argnames=("n_nodes", "batch", "max_steps", "stable",
                                   "placement", "emit_l", "with_steps",
                                   "slab", "interpret"))
def _sparse_loop(key, edge_src, edge_dst, edge_prob, positions=None, *,
                 n_nodes: int, batch: int, max_steps: int = 0,
                 stable: bool = False, placement=None, emit_l: int = 0,
                 with_steps: bool = False, csr=None, slab: int = 0,
                 interpret: bool = False):
    """Edge-list frontier expansion as a pull (the ``sparse`` backend).

    An edge ``u -> v`` is consulted when ``v`` is in the reverse
    frontier (each vertex fronts at most once, so each edge gets exactly
    one coin — independent-inclusion triggering, any `CoinModel`).  The
    loop holds ``frontier`` and ``visited`` as ``(n, K)``, vertices on
    rows and the batch's rows on lanes, and walks the edges in CSR order
    (``csr``: the CSC edge positions stably sorted by source, derived
    here when absent).  Each step reads every edge's head frontier row,
    ``frontier[dst]`` (a row gather), keeps the edges whose coin fires,
    and ORs each vertex's contiguous run of out-edges into it
    (`repro.kernels.ops.segment_or`): no scatter and no transpose inside
    the loop.  The state is transposed to ``(K, n)`` once, after it.

    Coins are those of the CSC layout, drawn where the edge sits:
    positional coins are element ``(b, csc position)`` of ``uniform(s,
    (batch, m))``, generated by counter (`_uniform_at`); stable coins key
    on the edge's *identity* ``u * n + v`` rather than its list
    position, so inserts/deletes renumber nothing; padded never-firing
    edges (see `_pad_edges_pow2`) are likewise invisible.

    Under a placement the loop runs per device (``shard_map``): lanes
    split over the theta axes, vertex rows over the vertex axis.  Each
    device gathers the frontier along the vertex axis once a step (the
    frontier exchange, the loop's one collective besides the stop test)
    and pulls only into its own vertex block, over a window of ``slab``
    CSR edges (`_sparse_slab`; all ``m`` when absent) that holds the
    block's out-edges — the others in the window never fire — so the
    reduction stays local.

    ``emit_l > 0`` emits the batch *natively as index lists* ``(K,
    emit_l) int32`` (ascending, sentinel ``n_nodes``) instead of
    bitmaps — the C4 representation routed per-backend: the conversion
    fuses into this jit (the transient visited state never round-trips
    through an arena-sized bitmap write), and an `IndexStore` ingests the
    rows as-is (`add_index_batch`).  The coin stream is untouched, so
    emitted rows equal the bitmap rows converted after the fact, bit for
    bit.  Rows with more than ``emit_l`` members are truncated — callers
    grow ``emit_l`` and re-emit when a row comes back full (same key,
    same coins, wider lists).

    ``with_steps`` appends the while loop's trip count (int32): one
    step per BFS level reached, plus the step that finds the frontier
    empty, so one more than the deepest level of the batch's rows.
    """
    m = edge_src.shape[0]
    max_steps = max_steps or n_nodes
    if csr is None:
        csr = jnp.argsort(edge_src, stable=True)
    csr = csr.astype(jnp.int32)
    theta, vx = _mesh_axes(placement, positions)
    mesh = placement.mesh if placement is not None else None
    axes = tuple(x for ax in (theta, vx) if ax is not None
                 for x in (ax if isinstance(ax, tuple) else (ax,)))
    dt, dv = _axis_size(mesh, theta), _axis_size(mesh, vx)
    slab = slab if vx is not None and slab else m
    kstep, roots, _, bb = _setup(key, batch, n_nodes, positions, None,
                                 stable)
    K = roots.shape[0]
    kl, nl = -(-K // dt), -(-n_nodes // dv)     # lanes, rows per device
    hi_b, lo_b = _counter_base(kl * dt, m)
    lane_args = (jnp.pad(roots, (0, kl * dt - K), constant_values=-1),)
    if stable:
        lane_args += (jnp.pad(bb[:, 0], (0, kl * dt - K)),)

    def shard(src, dst, prob, pos, roots, bb=None):
        i = jax.lax.axis_index(theta) if theta is not None else 0
        j = jax.lax.axis_index(vx) if vx is not None else 0
        # this device's vertex block [j * nl, (j + 1) * nl): a window of
        # the CSR edges that holds its out-edges; the rest never fire
        at = jnp.clip(jnp.searchsorted(src, j * nl), 0, m - slab)
        win = lambda x: jax.lax.dynamic_slice_in_dim(x, at, slab)
        src, dst, pos = win(src), win(dst), win(pos)
        mine = (src >= j * nl) & (src < (j + 1) * nl)
        prob = jnp.where(mine, win(prob), 0.0)[:, None]
        lanes = lambda x: jax.lax.dynamic_slice_in_dim(x, i * kl, kl)
        if stable:
            uid = (src.astype(jnp.uint32) * jnp.uint32(n_nodes)
                   + dst.astype(jnp.uint32))[:, None]
            bb = lanes(bb)[None, :]
        else:
            words = _counter_words(lanes(jnp.asarray(hi_b)),
                                   lanes(jnp.asarray(lo_b)), pos)
        src = jnp.clip(src - j * nl, 0, nl - 1)
        visited0 = ((jnp.arange(nl, dtype=jnp.int32) + j * nl)[:, None]
                    == lanes(roots)[None, :])

        def cond(state):
            step, frontier, visited, _ = state
            more = frontier.any().astype(jnp.int32)
            if axes:
                more = jax.lax.psum(more, axes)
            return jnp.logical_and(step < max_steps, more > 0)

        def body(state):
            step, frontier, visited, key = state
            key, sub = jax.random.split(key)
            if stable:
                kd = jnp.asarray(sub, jnp.uint32).reshape(-1)
                coin = _u01(_mix32(_mix32(uid ^ kd[0]) ^ bb ^ kd[1]))
            else:
                coin = _uniform_at(sub, *words)
            if vx is not None:
                # the frontier exchange: an edge's head may be anywhere
                frontier = jax.lax.all_gather(frontier, vx, tiled=True)
            # reverse traversal: edge u->v is live when v is in the
            # frontier; u takes the OR of its contiguous out-edges
            live = frontier[dst] & (coin < prob)
            new = kops.segment_or(live, src, n=nl, interpret=interpret)
            new = jnp.logical_and(new, ~visited)
            return step + 1, new, jnp.logical_or(visited, new), key

        steps, _, visited, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), visited0, visited0, kstep))
        return visited, steps

    args = (edge_src[csr], edge_dst[csr], edge_prob[csr], csr) + lane_args
    if axes:
        rep = PartitionSpec()
        shard = shard_map(shard, mesh=mesh, in_specs=(rep,) * len(args),
                          out_specs=(PartitionSpec(vx, theta), rep))
    visited, steps = shard(*args)
    visited = visited[:n_nodes, :K].T
    if axes:
        visited = jax.lax.with_sharding_constraint(visited, placement)
    counter = visited.sum(axis=0, dtype=jnp.int32)
    if emit_l:
        out = (bitmap_to_indices(visited.astype(jnp.uint8), emit_l),
               counter, roots)
    else:
        out = (visited.astype(jnp.uint8), counter, roots)
    return out + (steps,) if with_steps else out


@partial(jax.jit, static_argnames=("batch", "max_steps", "max_indeg_log2",
                                   "stable", "placement", "with_steps"))
def _walk_loop(key, dst_offsets, in_src, in_cum, in_total, positions=None, *,
               batch: int, max_steps: int = 0, max_indeg_log2: int = 32,
               stable: bool = False, placement=None, with_steps: bool = False):
    """Pick-at-most-one random walk (the ``walk`` backend, `WalkModel`).

    Each step the walk at ``cur`` draws one uniform ``r``: ``r >=
    total(cur)`` stops, otherwise binary search over the per-dst
    cumulative weights selects the in-neighbor; revisits terminate.
    Stable draws key on the row identity so a row's walk is a function
    of itself plus the per-dst segments it visits.

    Under a 2D placement the visited rows are still born as shard-local
    column slices (the ``placement`` constraint partitions the one-hot
    scatter), but the walk tables stay replicated: a walk's next gather
    is data-dependent and uniformly random over vertices, so there is no
    block locality for a column partition to exploit — tables are
    O(m + n) scalars, not O(n^2).

    ``with_steps`` appends the while loop's trip count (int32): the
    longest walk's moves plus the step that stops it, which is the
    batch's largest row size.
    """
    n = dst_offsets.shape[0] - 1
    max_steps = max_steps or n
    kstep, roots, visited0, bb = _setup(
        key, batch, n, positions, placement, stable)
    brow = bb[:, 0] if stable else None

    def pick_in_neighbor(cur, r):
        """Binary search within CSC segment of ``cur`` for cum >= r."""
        lo = dst_offsets[cur]
        hi = dst_offsets[cur + 1]

        def step_fn(_, lohi):
            lo_, hi_ = lohi
            mid = (lo_ + hi_) // 2
            val = in_cum[jnp.clip(mid, 0, in_cum.shape[0] - 1)]
            go_right = val < r
            return (jnp.where(go_right, mid + 1, lo_),
                    jnp.where(go_right, hi_, mid))

        lo_f, _ = jax.lax.fori_loop(0, max_indeg_log2, step_fn, (lo, hi))
        idx = jnp.clip(lo_f, 0, in_src.shape[0] - 1)
        return in_src[idx]

    def cond(state):
        step, cur, active, visited, _ = state
        return jnp.logical_and(step < max_steps, active.any())

    def body(state):
        step, cur, active, visited, k = state
        k, sub = jax.random.split(k)
        if stable:
            kd = jnp.asarray(sub, jnp.uint32).reshape(-1)
            r = _u01(_mix32(_mix32(brow ^ kd[0]) ^ kd[1]))
        else:
            r = jax.random.uniform(sub, (batch,))
        total = in_total[cur]
        go = jnp.logical_and(active, r < total)
        nxt = jax.vmap(pick_in_neighbor)(cur, r)
        revisit = jnp.take_along_axis(visited, nxt[:, None], axis=1)[:, 0]
        go = jnp.logical_and(go, ~revisit)
        visited = jnp.logical_or(
            visited, jax.nn.one_hot(nxt, visited.shape[1], dtype=jnp.bool_)
            & go[:, None]
        )
        cur = jnp.where(go, nxt, cur)
        return step + 1, cur, go, visited, k

    steps, _, _, visited, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), roots, jnp.ones(roots.shape, jnp.bool_),
                     visited0, kstep)
    )
    counter = visited.sum(axis=0, dtype=jnp.int32)
    out = (visited.astype(jnp.uint8), counter, roots)
    return out + (steps,) if with_steps else out


# ------------------------------------------------- historical entry points ----
#
# The pre-decomposition function API, kept as thin wrappers over the
# unified loops (benchmarks and launch/steps.py call these directly).

def sample_ic_dense(key, logq, *, batch: int, max_steps: int = 0,
                    placement=None):
    """Positional dense log-semiring IC sampling (see `_dense_loop`)."""
    return _dense_loop(key, logq, batch=batch, max_steps=max_steps,
                       placement=placement)


def sample_ic_dense_stable(key, logq, positions=None, *, batch: int,
                           max_steps: int = 0, placement=None):
    """Identity-keyed dense sampling with ``positions`` row subsets."""
    return _dense_loop(key, logq, positions, batch=batch,
                       max_steps=max_steps, stable=True, placement=placement)


def sample_ic_sparse(key, edge_src, edge_dst, edge_prob, *, n_nodes: int,
                     batch: int, max_steps: int = 0, placement=None):
    """Positional edge-list IC sampling (see `_sparse_loop`)."""
    return _sparse_loop(key, edge_src, edge_dst, edge_prob,
                        n_nodes=n_nodes, batch=batch, max_steps=max_steps,
                        placement=placement)


def sample_ic_sparse_stable(key, edge_src, edge_dst, edge_prob,
                            positions=None, *, n_nodes: int, batch: int,
                            max_steps: int = 0, placement=None):
    """Edge-identity-keyed sparse sampling with ``positions`` subsets."""
    return _sparse_loop(key, edge_src, edge_dst, edge_prob, positions,
                        n_nodes=n_nodes, batch=batch, max_steps=max_steps,
                        stable=True, placement=placement)


def sample_lt(key, dst_offsets, in_src, in_lt_cum, in_lt_total, *,
              batch: int, max_steps: int = 0, max_indeg_log2: int = 32,
              placement=None):
    """Positional LT RRR random walk (see `_walk_loop`)."""
    return _walk_loop(key, dst_offsets, in_src, in_lt_cum, in_lt_total,
                      batch=batch, max_steps=max_steps,
                      max_indeg_log2=max_indeg_log2, placement=placement)


def sample_lt_stable(key, dst_offsets, in_src, in_lt_cum, in_lt_total,
                     positions=None, *, batch: int, max_steps: int = 0,
                     max_indeg_log2: int = 32, placement=None):
    """Identity-keyed LT walk with ``positions`` row subsets."""
    return _walk_loop(key, dst_offsets, in_src, in_lt_cum, in_lt_total,
                      positions, batch=batch, max_steps=max_steps,
                      max_indeg_log2=max_indeg_log2, stable=True,
                      placement=placement)


# -------------------------------------------------------------- backends ----

def _pad_edges_pow2(edge_src, edge_dst, edge_prob):
    """Pad CSC edge arrays to the next power of two with never-firing
    edges (prob 0, endpoints 0), so the stable sparse loop is traced per
    pow2 *bucket* of m rather than per exact m — a `GraphDelta` that
    changes the edge count inside the bucket reuses the compiled kernel
    instead of retracing.  Identity-keyed coins make the pad lanes
    invisible: a padded sampler's output is bitwise identical to the
    unpadded one's (pinned in tests/test_sampler_matrix.py)."""
    m = int(edge_src.shape[0])
    m_pad = next_pow2(m, 1)
    if m_pad == m:
        return edge_src, edge_dst, edge_prob
    pad = m_pad - m
    z = jnp.zeros((pad,), edge_src.dtype)
    return (jnp.concatenate([edge_src, z]),
            jnp.concatenate([edge_dst, jnp.zeros((pad,), edge_dst.dtype)]),
            jnp.concatenate([edge_prob, jnp.zeros((pad,), edge_prob.dtype)]))


def _states_work(fn, coins_per_step: int, graph, cfg):
    """Tag a bound sampler with the work it does (`TraversalBackend`).
    ``graph`` is None for walk models; ``in_degree`` is left None where
    a batch's consulted pairs, at most ``batch * m``, could pass int32."""
    fn.coins_per_step = int(coins_per_step)
    fn.in_degree = (graph.in_degree()
                    if graph is not None and cfg.batch * graph.m < 2**31
                    else None)
    return fn


@dataclasses.dataclass(frozen=True)
class TraversalBackend:
    """One way to execute an RRR traversal.

    ``family`` names the model family it can execute ("coins" or
    "walk"); ``bind(model, graph, cfg, *, stable, placement)`` does the
    per-graph preprocessing once (dense matrix, edge padding, walk
    tables) and returns the bound sampler: a callable of a PRNG key —
    plus a keyword-only ``positions`` row subset when ``stable`` —
    returning ``(visited (B, n) uint8, counter (n,) int32, roots (B,))``.

    The built-in backends' bound samplers also take ``with_steps=True``
    (the loop's trip count is then returned fourth) and state their
    work: ``coins_per_step``, the uniforms one loop step draws for a
    full batch, and for coin models ``in_degree``, the ``(n,) int32``
    in-degrees, so a batch consults ``colsum . in_degree`` (row, edge)
    pairs (each member fronts once and tries each in-edge once).  The
    sparse backend's also give ``walked``, the edges the devices of one
    theta shard walk a step (``Dv x slab`` under a vertex axis, else
    ``m``), and ``owned``, the ``m`` edges there are.
    """
    name: str
    family: str
    bind: Callable


def _bind_dense(model, graph: Graph, cfg, *, stable, placement,
                kernel=False):
    logq = logq_from_probs(graph, model.edge_probs(graph))
    interpret = bool(getattr(cfg, "pallas_interpret", False))
    # double-buffer the frontier all-gather on 2D placements (config-
    # gated for the overlap-on/off equivalence cells; _dense_loop drops
    # the flag on 1D/absent placements where there is no collective)
    overlap = bool(getattr(cfg, "overlap", True))
    if stable:
        fn = lambda key, positions=None, with_steps=False: _dense_loop(
            key, logq, positions, batch=cfg.batch, stable=True,
            kernel=kernel, interpret=interpret, placement=placement,
            overlap=overlap, with_steps=with_steps)
    else:
        fn = lambda key, with_steps=False: _dense_loop(
            key, logq, batch=cfg.batch, kernel=kernel, interpret=interpret,
            placement=placement, overlap=overlap, with_steps=with_steps)
    return _states_work(fn, cfg.batch * graph.n, graph, cfg)


def _bind_pallas(model, graph: Graph, cfg, *, stable, placement):
    return _bind_dense(model, graph, cfg, stable=stable,
                       placement=placement, kernel=True)


def _bind_sparse(model, graph: Graph, cfg, *, stable, placement):
    src, dst = graph.edge_src, graph.edge_dst
    prob = jnp.asarray(model.edge_probs(graph), jnp.float32)
    if stable:
        # pow2 padding is only bitwise-invisible under identity-keyed
        # coins; the positional coin layout is a function of m, so the
        # positional sampler keeps the exact edge count (seed parity
        # with the historical IC-sparse stream)
        src, dst, prob = _pad_edges_pow2(src, dst, prob)
    # the CSR order the loop walks its edges in, sorted once per graph,
    # and the window of it each vertex block of the placement walks
    csr = np.argsort(np.asarray(src), kind="stable").astype(np.int32)
    slab = _sparse_slab(src, graph.n, placement)
    kw = dict(n_nodes=graph.n, batch=cfg.batch, placement=placement,
              csr=jnp.asarray(csr), slab=slab,
              interpret=bool(getattr(cfg, "pallas_interpret", False)))
    if stable:
        fn = (lambda key, positions=None, emit_l=0, with_steps=False:
              _sparse_loop(key, src, dst, prob, positions, stable=True,
                           emit_l=emit_l, with_steps=with_steps, **kw))
    else:
        fn = lambda key, emit_l=0, with_steps=False: _sparse_loop(
            key, src, dst, prob, emit_l=emit_l, with_steps=with_steps, **kw)
    # the engine routes C4 per-backend through this tag: an IndexStore
    # asks a tagged sampler for native index rows (`emit_l`) instead of
    # densifying to bitmaps and converting at the arena write
    fn.supports_index_emit = True
    # the edges one theta shard's devices walk a step, a window of the
    # CSR edges per vertex block, against the edges there are (pad
    # edges included): one coin per (row, walked edge) a step
    fn.owned = int(src.shape[0])
    fn.walked = _axis_size(getattr(placement, "mesh", None),
                           _vertex_axis_of(placement)) * slab
    return _states_work(fn, cfg.batch * fn.walked, graph, cfg)


def _bind_walk(model, graph: Graph, cfg, *, stable, placement):
    tables = model.walk_tables(graph)
    if stable:
        fn = lambda key, positions=None, with_steps=False: _walk_loop(
            key, *tables, positions, batch=cfg.batch, stable=True,
            placement=placement, with_steps=with_steps)
    else:
        fn = lambda key, with_steps=False: _walk_loop(
            key, *tables, batch=cfg.batch, placement=placement,
            with_steps=with_steps)
    # one uniform per row a step
    return _states_work(fn, cfg.batch, None, cfg)


DENSE_BACKEND = TraversalBackend("dense", "coins", _bind_dense)
SPARSE_BACKEND = TraversalBackend("sparse", "coins", _bind_sparse)
PALLAS_BACKEND = TraversalBackend("pallas", "coins", _bind_pallas)
WALK_BACKEND = TraversalBackend("walk", "walk", _bind_walk)

_BACKEND_REGISTRY: dict = {}


def register_backend(backend: TraversalBackend) -> None:
    """Register a `TraversalBackend` under its name (overwrites
    silently)."""
    _BACKEND_REGISTRY[backend.name] = backend


def get_backend(name: str) -> TraversalBackend:
    try:
        return _BACKEND_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown traversal backend {name!r}; registered: "
            f"{sorted(_BACKEND_REGISTRY)}")


def registered_backends():
    return sorted(_BACKEND_REGISTRY)


for _b in (DENSE_BACKEND, SPARSE_BACKEND, PALLAS_BACKEND, WALK_BACKEND):
    register_backend(_b)


# ----------------------------------------------------------- composition ----

def _check_family(model, backend) -> None:
    if backend.family != model.family:
        raise ValueError(
            f"backend {backend.name!r} executes {backend.family!r}-family "
            f"models; model {model.name!r} is {model.family!r}-family "
            f"(coin models compose with dense/sparse/pallas, walk models "
            f"with walk)")


def composed_name(model: str, backend: str, stable: bool = False) -> str:
    """Canonical registry spelling of a composition:
    ``"<model>/<backend>"`` plus ``"+stable"`` for the identity-keyed
    form (e.g. ``"WC/sparse"``, ``"IC/pallas+stable"``)."""
    return f"{model}/{backend}" + ("+stable" if stable else "")


def make_sampler(model, backend=None, *, stable: bool = False):
    """Compose a `DiffusionModel` x `TraversalBackend` into a sampler
    factory (registry-compatible: ``factory(graph, cfg, *,
    placement=None) -> bound sampler``).

    ``model``/``backend`` are registry names or instances; ``backend``
    defaults to the model family's reference backend ("dense" for coin
    models, "walk" for walk models).  ``stable=True`` selects
    identity-keyed counter-mode coins with ``positions`` row-subset
    resampling (the delta-stable form streaming refresh requires).
    Incompatible families fail fast::

        make_sampler("WC", "pallas")           # weighted cascade on MXU
        make_sampler("IC", "sparse", stable=True)
        make_sampler(CoinModel("mine", f), "dense")
    """
    m = get_model(model) if isinstance(model, str) else model
    if backend is None:
        backend = "dense" if m.family == "coins" else "walk"
    b = get_backend(backend) if isinstance(backend, str) else backend
    _check_family(m, b)
    model_ref = model if isinstance(model, str) else m
    backend_ref = backend if isinstance(backend, str) else b

    def factory(graph: Graph, cfg, *, placement=None):
        # names re-resolve per bind, so register_model/register_backend
        # shadowing (the documented overwrite contract) reaches factories
        # composed — or cached by get_sampler — before the re-registration
        mm = (get_model(model_ref) if isinstance(model_ref, str)
              else model_ref)
        bb = (get_backend(backend_ref) if isinstance(backend_ref, str)
              else backend_ref)
        _check_family(mm, bb)
        return bb.bind(mm, graph, cfg, stable=stable, placement=placement)

    factory.__name__ = f"sampler_{m.name}_{b.name}" + (
        "_stable" if stable else "")
    factory.model, factory.backend, factory.stable = m, b, stable
    return factory


def sampler_matrix():
    """Every valid (model, backend) composition over the registered
    models and backends, as ``[(model_name, backend_name), ...]`` —
    the docs/tests/benchmarks iterate this instead of hardcoding."""
    cells = []
    for mn in registered_models():
        m = _MODEL_REGISTRY[mn]
        for bn in registered_backends():
            if _BACKEND_REGISTRY[bn].family == m.family:
                cells.append((mn, bn))
    return cells


# ------------------------------------------------------- sampler registry ----
#
# The engine resolves samplers by name so new diffusion models (or tuned
# variants of the built-ins) plug in without touching the driver:
#
#     register_model(CoinModel("mine", edge_prob_fn))   # every backend...
#     register_sampler("mine/dense", make_sampler("mine", "dense"))
#
# or, bypassing the axes entirely (a factory takes (graph, cfg) and
# returns a bound sampler; preprocessing happens once in the factory):
#
#     register_sampler("IC-mykernel", lambda graph, cfg: bound_fn)
#
# Factories may additionally accept a keyword-only ``placement`` (batch
# output sharding, see the module docstring); the engine passes it only
# to factories that declare it (`bind_sampler`), so user-registered
# (graph, cfg) factories keep working unchanged.

_SAMPLER_REGISTRY = {}

# historical monolithic spellings -> canonical compositions.  Resolving
# one emits a DeprecationWarning (once per name per process) pointing at
# the `make_sampler` spelling; results are seed-for-seed identical.
_LEGACY_ALIASES = {
    "IC-dense": "IC/dense",
    "IC-sparse": "IC/sparse",
    "LT": "LT/walk",
    "IC-dense-stable": "IC/dense+stable",
    "IC-sparse-stable": "IC/sparse+stable",
    "LT-stable": "LT/walk+stable",
}
_LEGACY_WARNED: set = set()


def register_sampler(name: str, factory=None):
    """Register a sampler factory under ``name`` (overwrites silently so
    experiments can shadow the built-ins).  Usable as a decorator:
    ``@register_sampler("IC-mykernel")``."""
    if factory is None:
        def deco(f):
            _SAMPLER_REGISTRY[name] = f
            return f
        return deco
    _SAMPLER_REGISTRY[name] = factory
    return factory


def _parse_composed(name: str):
    """``(model, backend, stable)`` when ``name`` is a canonical
    composition over *registered* axes, else None.  This is what lets a
    post-import ``register_model``/``register_backend`` resolve through
    configs immediately — its composed names need no pre-registration."""
    mdl, sep, rest = name.partition("/")
    if not sep:
        return None
    bkd, plus, stb = rest.partition("+")
    if plus and stb != "stable":
        return None
    if mdl in _MODEL_REGISTRY and bkd in _BACKEND_REGISTRY:
        return mdl, bkd, bool(plus)
    return None


def get_sampler(name: str):
    hit = _SAMPLER_REGISTRY.get(name)
    if hit is not None:
        return hit
    alias = _LEGACY_ALIASES.get(name)
    if alias is not None:
        if name not in _LEGACY_WARNED:
            _LEGACY_WARNED.add(name)
            mdl, _, rest = alias.partition("/")
            bkd, _, stb = rest.partition("+")
            spelling = f"make_sampler({mdl!r}, {bkd!r}" + (
                ", stable=True)" if stb else ")")
            warnings.warn(
                f"sampler name {name!r} is a legacy monolithic spelling; "
                f"use {alias!r} (= {spelling}) instead — results are "
                f"seed-for-seed identical",
                DeprecationWarning, stacklevel=2)
        return _SAMPLER_REGISTRY[alias]
    axes = _parse_composed(name)
    if axes is not None:
        # compose (and cache) on demand: models/backends registered
        # after import resolve by canonical name with no extra
        # register_sampler calls; family mismatches fail with
        # make_sampler's explanation
        mdl, bkd, stable = axes
        factory = make_sampler(mdl, bkd, stable=stable)
        _SAMPLER_REGISTRY[name] = factory
        return factory
    raise ValueError(
        f"unknown sampler {name!r}; registered: "
        f"{registered_samplers()}")


def registered_samplers():
    """All resolvable names: the canonical ``model/backend[+stable]``
    matrix, user registrations, and the deprecated legacy aliases."""
    return sorted(set(_SAMPLER_REGISTRY) | set(_LEGACY_ALIASES))


for _mn, _bn in sampler_matrix():
    for _s in (False, True):
        register_sampler(composed_name(_mn, _bn, _s),
                         make_sampler(_mn, _bn, stable=_s))


def default_sampler_name(graph: Graph, cfg) -> str:
    """Resolve ``cfg`` to a canonical composed name: coin models take the
    dense backend below ``cfg.dense_sampler_max_n`` and the edge-list
    backend above it (the historical dispatch), walk models take the
    walk backend; ``cfg.backend`` overrides the backend axis and
    ``cfg.stable`` selects the identity-keyed form."""
    m = get_model(cfg.model)
    backend = getattr(cfg, "backend", None)
    if backend is None:
        if m.family == "walk":
            backend = "walk"
        else:
            backend = ("dense" if graph.n <= cfg.dense_sampler_max_n
                       else "sparse")
    else:
        # fail here with the family explanation, not later with a
        # generic unknown-sampler error from the composed name
        _check_family(m, get_backend(backend))
    return composed_name(m.name, backend, bool(getattr(cfg, "stable",
                                                       False)))


def stable_variant(name: str) -> str:
    """The delta-stable spelling of a sampler name: canonical names gain
    ``+stable``, legacy aliases keep their legacy ``-stable`` spelling,
    and unknown (user-registered) names pass through unchanged — the
    caller keeps whatever row-resample support the custom factory has."""
    if name.endswith("+stable") or name.endswith("-stable"):
        return name
    if name in _LEGACY_ALIASES:
        return f"{name}-stable"
    if (f"{name}+stable" in _SAMPLER_REGISTRY
            or _parse_composed(name) is not None):
        return f"{name}+stable"
    return name


def bind_sampler(factory, graph: Graph, cfg, placement=None):
    """Instantiate a sampler factory, forwarding ``placement`` only when
    the factory declares it (keyword ``placement`` or ``**kwargs``) —
    back-compat with user factories registered as ``(graph, cfg)``."""
    if placement is not None:
        params = inspect.signature(factory).parameters
        takes_kw = any(p.kind is inspect.Parameter.VAR_KEYWORD
                       for p in params.values())
        if "placement" in params or takes_kw:
            return factory(graph, cfg, placement=placement)
    return factory(graph, cfg)
