"""Find_Most_Influential_Set (paper Alg. 2) — greedy max-coverage.

Every selection runs one pick loop, `_greedy`: count the sets that hold
each vertex once, then ``k`` times pick the vertex with the largest
count (first on ties), mark the alive sets that hold it covered, and
update the counter by one of two rules:

  * ``method="rebuild"`` — EfficientIMM (paper C5 "adaptive counter
    update"): after each pick but the last, count whichever is the
    smaller set of rows — the sets the pick newly covered (subtracted)
    or the sets alive after it (counted afresh).  Where a count reads
    only the rows it is given (`select_dense`'s row view, Pallas
    kernels in kernels/coverage_rows.py) no row is read for nothing;
    where a count is a full pass, either branch costs one pass.
  * ``method="decrement"`` — Ripples-faithful baseline: subtract the
    count of the newly covered sets after every pick, counted over the
    whole arena.

Counts are exact integers, so the two rules pick the same seeds with the
same gains (property-tested); their cost profiles differ exactly as the
paper describes — with skewed graphs most sets contain the first seeds,
so the decremental update touches far more rows.

Each at-rest form gives the loop its count and its pick:

  * bitmap rows on one chip (`select_dense`): the row view and the
    listed-rows kernel for ``rebuild``, `coverage_matvec` for
    ``decrement``;
  * bitmap, bit-packed or token rows (`_codec_tally`): counted by
    `repro.kernels.ops.arena_count`, never widened or decoded whole, the
    winner's membership read as one column — `select_packed` and
    `select_compressed` (`repro.core.pack.selection`) on one chip,
    `select_dense_sharded` per tile;
  * index lists (`_index_tally`) through `bincount_weighted` —
    `select_sparse` on one chip, `select_sparse_sharded` per tile.

On a mesh (paper C1 RRRset partitioning) the theta axis of the arena is
sharded and each device counts *its own resident tile* — a
`ShardedStore` view enters with the store's native layout, so no arena
data moves on entry.  The counter is the psum of the tiles' counts over
the theta axes (the paper's atomic global counter, vertex-sharded on 2D
meshes), and per pick only reduced quantities cross devices: the
counter, the per-vertex-shard argmax candidates and scalar gains; arena
rows never do.

The `SelectionStrategy` registry at the bottom exposes these to the
`InfluenceEngine` as ``<method>-<layout>`` names — rebuild/decrement x
dense/sparse/packed/compressed/sharded/sharded-sparse — so new
strategies plug in via ``register_selection`` instead of growing an
if/elif ladder in the driver.

Every strategy treats ``valid`` as an *arbitrary* row mask, not a prefix:
``alive`` starts from it, the counter reduction masks by it, and
``covered_frac`` normalizes by its popcount.  The streaming subsystem
(``repro.stream``) leans on exactly this contract — a `GraphDelta` clears
the live bits of stale RRR rows and they drop out of the very next
``select``/``hits`` with no rebuild and no kernel changes here.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.graphs.partition import vertex_partition
from repro.kernels import ops as kops
from repro.kernels.coverage_rows import vertex_ids
from repro.sparse.scatter import bincount_weighted


# ------------------------------------------------------------ the loop ----

def _one_chip(x):
    return x


def _greedy(k: int, valid, *, count, pick, rule: str, build=None,
            total=_one_chip):
    """The greedy pick loop every selection runs.

    ``count(mask) -> (counter, rows)``: the per-vertex counts of the
    ``mask`` rows and the arena rows counting them read.  ``build`` is
    the first count, of ``valid``; it defaults to ``count(valid)``.
    ``pick(counter, alive) -> (v, covered)``: the vertex with the
    largest count, first on ties, and the alive rows that hold it.
    ``total(x)`` sums a per-device scalar over the mesh's theta axes
    (the identity on one chip).

    ``rule="rebuild"`` updates the counter after each pick but the last
    from the smaller of the newly covered rows (subtracted) and the rows
    alive after the pick (counted afresh); ``rule="decrement"``
    subtracts the count of the covered rows after every pick.

    Returns (seeds (k,) int32, covered_frac () f32, gains (k,) int32,
    rows () int32).
    """
    if rule not in ("rebuild", "decrement"):
        raise ValueError(f"unknown method {rule}")
    counter, rows = count(valid) if build is None else build

    def body(i, state):
        alive, counter, rows, seeds, gains = state
        v, covered = pick(counter, alive)
        gain = total(covered.sum(dtype=jnp.int32))
        alive = alive & ~covered
        if rule == "rebuild":
            def update(counter):
                subtract = gain <= total(alive.sum(dtype=jnp.int32))
                part, read = count(jnp.where(subtract, covered, alive))
                return jnp.where(subtract, counter - part, part), read

            counter, read = jax.lax.cond(
                i < k - 1, update, lambda c: (c, jnp.int32(0)), counter)
        else:
            part, read = count(covered)
            counter = counter - part
        return (alive, counter, rows + read,
                seeds.at[i].set(v), gains.at[i].set(gain))

    _, _, rows, seeds, gains = jax.lax.fori_loop(
        0, k, body,
        (valid, counter, rows, jnp.zeros((k,), jnp.int32),
         jnp.zeros((k,), jnp.int32)),
    )
    n_valid = jnp.maximum(total(valid.sum(dtype=jnp.float32)), 1.0)
    covered_frac = gains.sum(dtype=jnp.float32) / n_valid
    return seeds, covered_frac, gains, rows


def _codec_tally(R, codec=None, interpret: bool = False):
    """``(tally, member)`` of an arena, or one tile of it, at rest in the
    form ``codec`` names (bitmap rows when None): ``tally(mask)`` is the
    ``(n_cols,) f32`` per-column count of the ``mask`` rows through
    `repro.kernels.ops.arena_count`, ``member(v)`` the ``(rows,) bool``
    membership of column ``v``, a one-column read."""
    def tally(mask):
        return kops.arena_count(R, mask, codec=codec, interpret=interpret)

    def member(v):
        if codec is None or codec.kind == "bitmap":
            return R[:, v] > 0
        return codec.decode_cols(R, v.reshape(1))[:, 0]

    return tally, member


def _index_tally(R_idx, n: int):
    """``(tally, member)`` of index lists ``(rows, L) int32`` with
    sentinel ``n`` padding: counts through `bincount_weighted`,
    membership as a list scan."""
    def tally(mask):
        return bincount_weighted(R_idx, mask.astype(jnp.float32)[:, None], n)

    def member(v):
        return (R_idx == v).any(axis=1)

    return tally, member


def _full_pass(k, valid, rule, tally, member, *, axes=None,
               vertex_axis=None, n=None, starts=None):
    """`_greedy` whose every count is one ``tally`` pass over the arena,
    or over one tile of it inside shard_map (``axes`` the theta axes;
    ``vertex_axis``, ``n`` and ``starts`` as `_vertex_sharded_pick`
    takes them).  The counter is kept global: the tiles' counts psummed
    over ``axes``."""
    total = _one_chip if axes is None else partial(jax.lax.psum,
                                                   axis_name=axes)
    rows = jnp.int32(valid.shape[0])

    def count(mask):
        return total(tally(mask)), rows

    def pick(counter, alive):
        if vertex_axis is not None:
            return _vertex_sharded_pick(
                counter, alive, n, vertex_axis, member, starts)
        v = jnp.argmax(counter).astype(jnp.int32)
        return v, member(v) & alive

    return _greedy(k, valid, count=count, pick=pick, rule=rule,
                   total=total)


# ---------------------------------------------------------------- dense ----

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseSelection:
    """What `select_dense` returns.  It reads as the triple ``(seeds,
    covered_frac, gains)`` that every selection strategy returns, and
    also carries ``rows``: the arena rows the call's counter build and
    updates read, a device scalar."""
    seeds: jax.Array
    covered_frac: jax.Array
    gains: jax.Array
    rows: jax.Array

    def _triple(self):
        return self.seeds, self.covered_frac, self.gains

    def __iter__(self):
        return iter(self._triple())

    def __len__(self):
        return 3

    def __getitem__(self, i):
        return self._triple()[i]


@partial(jax.jit, static_argnames=("k", "method"))
def select_dense(R, valid, k: int, method: str = "rebuild"):
    """R: (theta, n) uint8 0/1 bitmaps; valid: (theta,) bool (generated
    sets).

    Single-device (arrays replicated / unsharded); ``valid`` may be any
    mask.  ``method="rebuild"`` reads the arena once into a set-major
    row view (`repro.kernels.ops.row_view`, which counts the ``valid``
    rows in the same pass: the counter build), then updates the counter
    after each pick but the last from whichever is smaller: the ``c``
    rows the pick newly covered (subtracted) or the ``a`` rows alive
    after it (counted afresh), subtracting when ``c <= a``.  Each update
    reads just those rows of the view (`repro.kernels.ops.coverage_rows`),
    so a call reads ``theta + sum(min(c, a))`` rows; counts are exact
    int32, so seeds and gains equal a full rebuild's, first index on
    ties.
    ``method="decrement"`` counts the covered rows over the whole arena
    every pick (`coverage_matvec`, Pallas on TPU, the jnp oracle
    elsewhere), ``k + 1`` passes.

    Returns a `DenseSelection`: (seeds (k,) int32, covered_frac () f32,
    gains (k,) int32), and ``rows`` () int32.
    """
    theta, n = R.shape
    if method != "rebuild":
        return DenseSelection(*_full_pass(k, valid, method,
                                          *_codec_tally(R)))
    W, counter = kops.row_view(R, valid)
    vertex = vertex_ids(W.shape[1])

    def count(mask):
        return kops.coverage_rows(mask, W), mask.sum(dtype=jnp.int32)

    def pick(counter, alive):
        # argmax over vertices, first on ties (padding counts 0 and
        # follows every vertex)
        v = jnp.min(jnp.where(counter == counter.max(), vertex, n))
        return v, (R[:, v] > 0) & alive

    return DenseSelection(*_greedy(k, valid, count=count, pick=pick,
                                   rule=method,
                                   build=(counter, jnp.int32(theta))))


# --------------------------------------------------------------- sparse ----

@partial(jax.jit, static_argnames=("n", "k", "method"))
def select_sparse(R_idx, valid, n: int, k: int, method: str = "rebuild"):
    """R_idx: (theta, L) int32 with sentinel ``n`` padding; valid:
    (theta,) bool.  Single-device.  Returns (seeds (k,) int32,
    covered_frac () f32, gains (k,) int32)."""
    return _full_pass(k, valid, method, *_index_tally(R_idx, n))[:3]


# -------------------------------------------------------------- sharded ----

def _vertex_sharded_pick(counter, alive, n, vertex_axis, member_local,
                         starts=None):
    """Greedy argmax over a *vertex-sharded* counter -> (v, covered).

    Runs inside shard_map on every (theta, vertex) tile: mask padding
    columns out of the race, take the local argmax, resolve the global
    winner from ``Dv`` all-gathered (value, global id) scalar pairs, then
    test membership of the winner tile-locally — ``member_local(lv)``
    returns the ``(rows_local,) bool`` membership of in-range local id
    ``lv`` (its result is discarded for out-of-block winners) — and
    psum-or the bits over the vertex axis.  Shared by the dense and
    sharded-sparse strategies so their argmax/pad/tie-break semantics can
    never diverge.

    ``starts`` is the replicated ``(Dv + 1,) int32`` block-boundary array
    of the arena's `VertexPartition` (shard ``s`` owns global vertices
    ``[starts[s], starts[s+1])``) — it carries both the local->global id
    offset and the per-shard pad mask, for equal *and* edge-balanced
    layouts.  Because blocks are contiguous ascending runs in both
    layouts, per-shard-first argmax + first-shard-with-max resolution
    equals the unsharded first-argmax exactly, so selections are
    layout-invariant.  ``starts=None`` keeps the legacy arithmetic
    (equal blocks of width ``nloc``, pad mask from ``n``).
    """
    nloc = counter.shape[0]
    shard = jax.lax.axis_index(vertex_axis)
    if starts is not None:
        lo = starts[shard].astype(jnp.int32)
        size = starts[shard + 1].astype(jnp.int32) - lo
    else:
        lo = (shard * nloc).astype(jnp.int32)
        size = (jnp.clip(n - lo, 0, nloc).astype(jnp.int32)
                if n is not None else jnp.int32(nloc))
    counter = jnp.where(jnp.arange(nloc) < size, counter, -1.0)
    vloc = jnp.argmax(counter)
    val = counter[vloc]
    gidx = lo + vloc
    vals = jax.lax.all_gather(val, vertex_axis)
    gidxs = jax.lax.all_gather(gidx, vertex_axis)
    v = gidxs[jnp.argmax(vals)].astype(jnp.int32)
    lv = v - lo
    member = member_local(jnp.clip(lv, 0, nloc - 1))
    member = jnp.where((lv >= 0) & (lv < nloc), member, False)
    member = jax.lax.psum(member.astype(jnp.int32), vertex_axis) > 0
    return v, member & alive


def _starts_for(mesh, vertex_axis, n, partition):
    """Replicated ``(Dv + 1,) int32`` block boundaries for the sharded
    pick, or None when there is no vertex axis (1D layouts never remap
    ids) or no way to build them (``n`` and ``partition`` both absent —
    the legacy unmasked path)."""
    if vertex_axis is None:
        return None
    if partition is None:
        if n is None:
            return None
        partition = vertex_partition(int(n), int(mesh.shape[vertex_axis]))
    return jnp.asarray(partition.starts, jnp.int32)


def _on_mesh(mesh, R, valid, k, method, provider, *, theta_axes,
             vertex_axis, n, starts):
    """`_full_pass` over every ``(theta, vertex)`` tile of ``R``, the
    tile's ``(tally, member)`` from ``provider(tile)``; ``starts``, when
    not None, enters replicated.  Returns the replicated (seeds,
    covered_frac, gains)."""
    axes = tuple(theta_axes)

    def local_select(R_local, valid_local, starts=None):
        return _full_pass(k, valid_local, method, *provider(R_local),
                          axes=axes, vertex_axis=vertex_axis, n=n,
                          starts=starts)[:3]

    args, specs = (R, valid), (P(axes, vertex_axis), P(axes))
    if starts is not None:
        args, specs = args + (starts,), specs + (P(),)
    fn = shard_map(local_select, mesh=mesh, in_specs=specs,
                   out_specs=(P(), P(), P()))
    return fn(*args)


def select_dense_sharded(mesh, R, valid, k: int, *,
                         theta_axes=("data",), vertex_axis=None,
                         method: str = "rebuild", n: int | None = None,
                         partition=None, codec=None,
                         interpret: bool = False):
    """EfficientIMM selection with the theta axis sharded over ``theta_axes``
    (paper C1) and, optionally, the vertex axis over ``vertex_axis``.

    ``R (theta, n_pad)`` and ``valid (theta,) bool`` enter with specs
    ``P(theta_axes, vertex_axis)`` / ``P(theta_axes)`` — a
    `ShardedStore` view already carries exactly this layout (1D stores
    with ``vertex_axis=None``, 2D stores with the vertex axis resident),
    so its arena tiles are consumed in place; replicated arrays are
    scattered on entry.  ``R`` holds bitmap rows, or the tiles of the
    form ``codec`` names.  ``valid`` may be any mask, not just a prefix
    — sharded stores fill each shard independently.  ``n`` is the real
    vertex count: on 2D layouts the column dimension is padded to
    ``Dv * n_local`` and the pad columns must never win the argmax
    (they are all-zero, but an all-zero round would otherwise pick one).
    ``partition`` is the arena's `VertexPartition` — it must match the
    layout the columns of ``R`` were tiled with (a `ShardedStore` exposes
    it as ``store.partition``); when None the canonical equal-block
    layout for ``n`` is assumed.

    Inside shard_map each device owns a ``(theta_local, n_local)`` tile
    and runs `_greedy` over it: its counts go through
    `repro.kernels.ops.arena_count` (``interpret=True`` runs the Pallas
    kernels through the interpreter), so no tile is ever widened or
    whole-tile decoded, and are psummed over the theta axes into the
    global counter; the argmax resolves over the vertex axis from ``Dv``
    all-gathered candidates, the winner's membership is a one-column
    read psum-or'ed over the vertex axis, and gains are psummed — never
    arena rows or columns.  The pick is computed redundantly on every
    device (cheap, avoids a broadcast).  Both rules are exact over
    integer-valued f32 counts and return identical selections.

    Returns replicated ``(seeds (k,) int32, covered_frac () f32,
    gains (k,) int32)``.
    """
    return _on_mesh(
        mesh, R, valid, k, method,
        lambda tile: _codec_tally(tile, codec, interpret),
        theta_axes=theta_axes, vertex_axis=vertex_axis, n=n,
        starts=_starts_for(mesh, vertex_axis, n, partition))


def select_sparse_sharded(mesh, R_idx, valid, n: int, k: int, *,
                          theta_axes=("data",), vertex_axis=None,
                          method: str = "rebuild", partition=None):
    """Greedy max-coverage over *sharded index lists* — the C4 sparse
    representation on a 1D or 2D mesh.

    ``R_idx (Dt * cap_local, Dv * l_pad) int32`` enters with spec
    ``P(theta_axes, vertex_axis)``: tile ``(t, v)`` holds, for each of
    its rows, the *local* ids (``0 .. n_local-1``, sentinel ``n_local``)
    of the set members that fall inside vertex block ``v`` — exactly what
    `ShardedStore.index_view` emits (each vertex shard applied the C4
    width to its own columns).  ``valid (Dt * cap_local,) bool`` is
    ``P(theta_axes)``.

    Each tile bincounts its own lists into an ``(n_local,)`` count, and
    the rest is `select_dense_sharded`'s loop: the psum over the theta
    axes keeps the counter vertex-sharded, the argmax crosses the vertex
    axis as ``Dv`` (value, index) scalars, and membership of the winner
    is a tile-local list scan psum-or'ed over the vertex axis.
    Selections are identical to the dense strategies over the same rows
    (exact integer counts).

    Returns replicated ``(seeds (k,) int32, covered_frac () f32,
    gains (k,) int32)``.
    """
    Dv = int(mesh.shape[vertex_axis]) if vertex_axis else 1
    # the vertex-block layout — must match the tiles
    # ShardedStore.index_view emitted, or local ids mean the wrong vertex
    part = partition if partition is not None else vertex_partition(n, Dv)
    return _on_mesh(
        mesh, R_idx, valid, k, method,
        lambda tile: _index_tally(tile, part.block),
        theta_axes=theta_axes, vertex_axis=vertex_axis, n=n,
        starts=_starts_for(mesh, vertex_axis, n, part))


# ------------------------------------------------- SelectionStrategy API ----
#
# A strategy is ``fn(view, k, **opts) -> (seeds, covered_frac, gains)`` where
# ``view`` is a ``repro.core.store.StoreView`` (duck-typed: .R, .valid, .n).
# The registry is keyed "<method>-<layout>" with method in
# {rebuild, decrement} and layout in {dense, sparse, packed, compressed,
# sharded, sharded-sparse} (packed and compressed registered by
# ``repro.core.pack.selection``).

SELECTION_STRATEGIES = {}


def register_selection(name: str, fn=None):
    """Register a selection strategy; usable as ``@register_selection(name)``."""
    if fn is None:
        def deco(f):
            SELECTION_STRATEGIES[name] = f
            return f
        return deco
    SELECTION_STRATEGIES[name] = fn
    return fn


def get_selection(method: str, layout: str):
    name = f"{method}-{layout}"
    try:
        return SELECTION_STRATEGIES[name]
    except KeyError:
        raise ValueError(
            f"no selection strategy {name!r}; registered: "
            f"{sorted(SELECTION_STRATEGIES)}")


def _dense_strategy(method):
    def run(view, k, **_):
        return select_dense(view.R, view.valid, k, method)
    return run


def _sparse_strategy(method):
    def run(view, k, **_):
        return select_sparse(view.R, view.valid, view.n, k, method)
    return run


def _sharded_strategy(method):
    def run(view, k, *, mesh=None, theta_axes=("data",), vertex_axis=None,
            partition=None, codec=None, pallas_interpret=False, **_):
        if mesh is None:
            raise ValueError("sharded selection needs a mesh")
        return select_dense_sharded(
            mesh, view.R, view.valid, k,
            theta_axes=theta_axes, vertex_axis=vertex_axis, method=method,
            n=view.n, partition=partition, codec=codec,
            interpret=bool(pallas_interpret))
    return run


def _sharded_sparse_strategy(method):
    def run(view, k, *, mesh=None, theta_axes=("data",), vertex_axis=None,
            partition=None, **_):
        if mesh is None:
            raise ValueError("sharded selection needs a mesh")
        return select_sparse_sharded(
            mesh, view.R, view.valid, view.n, k,
            theta_axes=theta_axes, vertex_axis=vertex_axis, method=method,
            partition=partition)
    return run


for _m in ("rebuild", "decrement"):
    register_selection(f"{_m}-dense", _dense_strategy(_m))
    register_selection(f"{_m}-sparse", _sparse_strategy(_m))
    register_selection(f"{_m}-sharded", _sharded_strategy(_m))
    register_selection(f"{_m}-sharded-sparse", _sharded_sparse_strategy(_m))


# ------------------------------------------- Ripples-faithful baseline ----

@partial(jax.jit, static_argnames=("n", "k"))
def select_vertex_partitioned(R_idx, valid, n: int, k: int):
    """The Ripples work pattern the paper profiles (§III Challenge 1):
    vertices are partitioned across workers and every worker BINARY-SEARCHES
    every (sorted) RRRset for its vertices — O(n * theta * log L) loads per
    counter build vs EfficientIMM's O(theta * L) scatter.  Used as the
    memory-traffic baseline in benchmarks/table4_memory.py.

    R_idx: (theta, L) ascending index lists, sentinel ``n`` padding.
    """
    theta, L = R_idx.shape

    def contains_v(v):
        pos = jnp.clip(
            jax.vmap(lambda row: jnp.searchsorted(row, v))(R_idx), 0, L - 1)
        return jnp.take_along_axis(R_idx, pos[:, None], 1)[:, 0] == v

    def counter_of(alive):
        return jax.vmap(
            lambda v: jnp.sum(contains_v(v) & alive, dtype=jnp.float32)
        )(jnp.arange(n))

    counter0 = counter_of(valid)

    def body(i, state):
        alive, counter, seeds, gains = state
        v = jnp.argmax(counter).astype(jnp.int32)
        covered = contains_v(v) & alive
        gain = covered.sum(dtype=jnp.int32)
        # decremental update: re-search every covered set per vertex
        dec = jax.vmap(
            lambda u: jnp.sum(contains_v(u) & covered, dtype=jnp.float32)
        )(jnp.arange(n))
        return (alive & ~covered, counter - dec,
                seeds.at[i].set(v), gains.at[i].set(gain))

    alive, counter, seeds, gains = jax.lax.fori_loop(
        0, k, body,
        (valid, counter0, jnp.zeros((k,), jnp.int32),
         jnp.zeros((k,), jnp.int32)))
    n_valid = jnp.maximum(valid.sum(dtype=jnp.float32), 1.0)
    return seeds, gains.sum(dtype=jnp.float32) / n_valid, gains
