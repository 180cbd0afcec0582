"""Find_Most_Influential_Set (paper Alg. 2) — greedy max-coverage.

Two strategies, both over bitmap ``R (theta, n) uint8`` or index-list
``R_idx (theta, L) int32`` representations:

  * ``method="rebuild"``   — EfficientIMM (paper C5 "adaptive counter
    update"): after one counter build, each pick updates the counter
    from whichever is the smaller set of rows — subtract the sets it
    newly covered, or rebuild from the sets that survive it — so no row
    is read for nothing.  The dense version (`select_dense`) reads only
    those rows (Pallas kernels: kernels/coverage_rows.py); the sparse,
    sharded and fused ones rebuild from every surviving set each pick
    (``counter = alive @ R``, kernels/coverage_matvec.py /
    fused_select.py).
  * ``method="decrement"`` — Ripples-faithful baseline: keep a running
    counter and subtract the contribution of the sets covered by the newly
    selected seed, counted over the whole arena.

The two are algebraically identical (property-tested); their cost profiles
differ exactly as the paper describes — with skewed graphs most sets contain
the first seeds, so the decremental update touches far more rows.

``select_dense_sharded`` is the multi-device version (paper C1 RRRset
partitioning, end-to-end since the `ShardedStore` rework): the theta axis
of ``R`` is sharded across the mesh and each device reduces over *its own
resident arena shard* — when fed a ``ShardedStore`` view the input specs
match the store's native ``P(theta_axes, None)`` layout, so no arena data
moves on entry.  Per greedy round only reduced quantities cross devices
(the ``(n,)`` counter psum standing in for the paper's atomic adds, and a
scalar gain); arena rows never do.  Both counter-update methods exist as
true implementations here: ``rebuild`` re-reduces the surviving local rows
every round (C5), ``decrement`` keeps a *local partial counter* per shard
and subtracts the covered local rows' contribution — the running-counter
baseline, executed shard-locally.

The `SelectionStrategy` registry at the bottom exposes all of these to the
`InfluenceEngine` as ``(method, layout)`` pairs — rebuild/decrement x
dense/sparse/sharded — so new strategies plug in via ``register_selection``
instead of growing an if/elif ladder in the driver.

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
    if method == "rebuild":
        W, counter = kops.row_view(R, valid)
        vertex = vertex_ids(W.shape[1])

        def count(mask):
            return kops.coverage_rows(mask, W), mask.sum(dtype=jnp.int32)

        def body(i, state):
            alive, counter, rows, seeds, gains = state
            # argmax over vertices, first on ties (padding counts 0 and
            # follows every vertex)
            v = jnp.min(jnp.where(counter == counter.max(), vertex, n))
            covered = (R[:, v] > 0) & alive
            gain = covered.sum(dtype=jnp.int32)
            alive = alive & ~covered

            def update(counter):
                subtract = gain <= alive.sum(dtype=jnp.int32)
                part, read = count(jnp.where(subtract, covered, alive))
                return jnp.where(subtract, counter - part, part), read

            counter, read = jax.lax.cond(
                i < k - 1, update, lambda c: (c, jnp.int32(0)), counter)
            return (alive, counter, rows + read,
                    seeds.at[i].set(v), gains.at[i].set(gain))

        alive, _, rows, seeds, gains = jax.lax.fori_loop(
            0, k, body,
            (valid, counter, jnp.int32(theta),
             jnp.zeros((k,), jnp.int32), jnp.zeros((k,), jnp.int32)),
        )
    elif method == "decrement":
        def counter_of(rows):
            return kops.coverage_matvec(rows.astype(jnp.float32), R)

        def body(i, state):
            alive, counter, seeds, gains = state
            v = jnp.argmax(counter).astype(jnp.int32)
            covered = (R[:, v] > 0) & alive
            gain = covered.sum(dtype=jnp.int32)
            counter = counter - counter_of(covered)
            return (alive & ~covered, counter,
                    seeds.at[i].set(v), gains.at[i].set(gain))

        alive, _, seeds, gains = jax.lax.fori_loop(
            0, k, body,
            (valid, counter_of(valid), jnp.zeros((k,), jnp.int32),
             jnp.zeros((k,), jnp.int32)),
        )
        rows = jnp.int32((k + 1) * theta)
    else:
        raise ValueError(f"unknown method {method}")

    n_valid = jnp.maximum(valid.sum(dtype=jnp.float32), 1.0)
    covered_frac = gains.sum(dtype=jnp.float32) / n_valid
    return DenseSelection(seeds, covered_frac, gains, rows)


# --------------------------------------------------------------- sparse ----

@partial(jax.jit, static_argnames=("n", "k", "method"))
def select_sparse(R_idx, valid, n: int, k: int, method: str = "rebuild"):
    """R_idx: (theta, L) int32 with sentinel ``n`` padding; valid:
    (theta,) bool.  Single-device.  Returns (seeds (k,) int32,
    covered_frac () f32, gains (k,) int32)."""
    theta, L = R_idx.shape

    def counter_of(alive):
        return bincount_weighted(R_idx, alive.astype(jnp.float32)[:, None], n)

    def contains(v):
        return (R_idx == v).any(axis=1)

    if method == "rebuild":
        def body(i, state):
            alive, seeds, gains = state
            counter = counter_of(alive)
            v = jnp.argmax(counter).astype(jnp.int32)
            covered = contains(v) & alive
            gain = covered.sum(dtype=jnp.int32)
            return alive & ~covered, seeds.at[i].set(v), gains.at[i].set(gain)

        alive, seeds, gains = jax.lax.fori_loop(
            0, k, body,
            (valid, jnp.zeros((k,), jnp.int32), jnp.zeros((k,), jnp.int32)),
        )
    elif method == "decrement":
        counter0 = counter_of(valid)

        def body(i, state):
            alive, counter, seeds, gains = state
            v = jnp.argmax(counter).astype(jnp.int32)
            covered = contains(v) & alive
            gain = covered.sum(dtype=jnp.int32)
            counter = counter - bincount_weighted(
                R_idx, covered.astype(jnp.float32)[:, None], n)
            return (alive & ~covered, counter,
                    seeds.at[i].set(v), gains.at[i].set(gain))

        alive, _, seeds, gains = jax.lax.fori_loop(
            0, k, body,
            (valid, counter0, jnp.zeros((k,), jnp.int32),
             jnp.zeros((k,), jnp.int32)),
        )
    else:
        raise ValueError(f"unknown method {method}")

    n_valid = jnp.maximum(valid.sum(dtype=jnp.float32), 1.0)
    return seeds, gains.sum(dtype=jnp.float32) / n_valid, gains


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


def select_dense_sharded(mesh, R, valid, k: int, *,
                         theta_axes=("data",), vertex_axis=None,
                         method: str = "rebuild", n: int | None = None,
                         partition=None, codec=None,
                         interpret: bool = False):
    """EfficientIMM selection with the theta axis sharded over ``theta_axes``
    (paper C1) and, optionally, the vertex axis over ``vertex_axis``.

    ``R (theta, n_pad) uint8`` and ``valid (theta,) bool`` enter with
    specs ``P(theta_axes, vertex_axis)`` / ``P(theta_axes)`` — a
    `ShardedStore` view already carries exactly this layout (1D stores
    with ``vertex_axis=None``, 2D stores with the vertex axis resident),
    so its arena tiles are consumed in place; replicated arrays are
    scattered on entry.  ``valid`` may be any mask, not just a prefix —
    sharded stores fill each shard independently.  ``n`` is the real
    vertex count: on 2D layouts the column dimension is padded to
    ``Dv * n_local`` and the pad columns must never win the argmax
    (they are all-zero, but an all-zero round would otherwise pick one).
    ``partition`` is the arena's `VertexPartition` — it must match the
    layout the columns of ``R`` were tiled with (a `ShardedStore` exposes
    it as ``store.partition``); when None the canonical equal-block
    layout for ``n`` is assumed.

    Inside shard_map each device owns a ``(theta_local, n_local)`` tile.
    Per greedy round only reduced quantities cross devices: the counter
    ``psum`` over the theta axis (the paper's atomic global counter,
    staying vertex-sharded), the per-vertex-shard argmax candidates
    (``all_gather`` of ``Dv`` scalars), the covered-rows bits psum-or over
    the vertex axis, and the scalar gain — never arena rows or columns.
    The greedy argmax is computed redundantly on every device (cheap,
    avoids a broadcast).

    Every per-tile reduction runs through the `repro.kernels.ops`
    dispatch: bitmap tiles reduce with `coverage_matvec`, packed and
    compressed tiles with the decode-and-count kernels (``interpret=True``
    runs them through the Pallas interpreter), so no tile is ever widened
    or whole-tile decoded; membership of the winner is a one-column read
    (``decode_cols`` on encoded tiles).

    ``method="rebuild"`` re-reduces the surviving local rows every round
    (C5).  ``method="decrement"`` is the true decremental update executed
    tile-locally: each device keeps a partial counter over its own rows
    and columns and subtracts the contribution of its newly-covered rows,
    so the running global counter is ``psum`` of partials.  Both are
    exact over integer-valued f32 counts and return identical selections.

    Returns replicated ``(seeds (k,) int32, covered_frac () f32,
    gains (k,) int32)``.
    """
    axes = tuple(theta_axes)
    if method not in ("rebuild", "decrement"):
        raise ValueError(f"unknown method {method}")
    starts_arr = _starts_for(mesh, vertex_axis, n, partition)
    kind = "bitmap" if codec is None else codec.kind

    def local_select(R_local, valid_local, starts=None):
        def partial_of(alive):
            return kops.arena_count(R_local, alive, codec=codec,
                                    interpret=interpret)

        def member_local(lv):
            if kind == "bitmap":
                return R_local[:, lv] > 0
            return codec.decode_cols(R_local, lv.reshape(1))[:, 0]

        def pick(counter, alive):
            if vertex_axis is not None:
                return _vertex_sharded_pick(
                    counter, alive, n, vertex_axis, member_local, starts)
            v = jnp.argmax(counter).astype(jnp.int32)
            return v, member_local(v) & alive

        if method == "rebuild":
            def body(i, state):
                alive, seeds, gains = state
                counter = jax.lax.psum(partial_of(alive), axes)
                v, covered = pick(counter, alive)
                gain = jax.lax.psum(covered.sum(dtype=jnp.int32), axes)
                return (alive & ~covered,
                        seeds.at[i].set(v), gains.at[i].set(gain))

            alive, seeds, gains = jax.lax.fori_loop(
                0, k, body,
                (valid_local, jnp.zeros((k,), jnp.int32),
                 jnp.zeros((k,), jnp.int32)),
            )
        else:
            def body(i, state):
                alive, partial, seeds, gains = state
                counter = jax.lax.psum(partial, axes)
                v, covered = pick(counter, alive)
                gain = jax.lax.psum(covered.sum(dtype=jnp.int32), axes)
                partial = partial - partial_of(covered)
                return (alive & ~covered, partial,
                        seeds.at[i].set(v), gains.at[i].set(gain))

            alive, _, seeds, gains = jax.lax.fori_loop(
                0, k, body,
                (valid_local, partial_of(valid_local),
                 jnp.zeros((k,), jnp.int32), jnp.zeros((k,), jnp.int32)),
            )
        n_valid = jnp.maximum(
            jax.lax.psum(valid_local.sum(dtype=jnp.float32), axes), 1.0)
        return seeds, gains.sum(dtype=jnp.float32) / n_valid, gains

    out_specs = (P(), P(), P())
    if starts_arr is None:
        fn = shard_map(
            local_select, mesh=mesh,
            in_specs=(P(axes, vertex_axis), P(axes)), out_specs=out_specs,
        )
        return fn(R, valid)
    fn = shard_map(
        local_select, mesh=mesh,
        in_specs=(P(axes, vertex_axis), P(axes), P()), out_specs=out_specs,
    )
    return fn(R, valid, starts_arr)


def select_sparse_sharded(mesh, R_idx, valid, n: int, k: int, *,
                          theta_axes=("data",), vertex_axis=None,
                          method: str = "rebuild", partition=None):
    """Greedy max-coverage over *sharded index lists* — the C4 sparse
    representation on a 1D or 2D mesh, lifting the old bitmap-only
    restriction of the sharded pipeline.

    ``R_idx (Dt * cap_local, Dv * l_pad) int32`` enters with spec
    ``P(theta_axes, vertex_axis)``: tile ``(t, v)`` holds, for each of
    its rows, the *local* ids (``0 .. n_local-1``, sentinel ``n_local``)
    of the set members that fall inside vertex block ``v`` — exactly what
    `ShardedStore.index_view` emits (each vertex shard applied the C4
    width to its own columns).  ``valid (Dt * cap_local,) bool`` is
    ``P(theta_axes)``.

    Per greedy round each tile bincounts its own lists into an
    ``(n_local,)`` partial; the psum over the theta axis keeps the
    counter vertex-sharded, the argmax crosses the vertex axis as ``Dv``
    (value, index) scalars, and membership of the winner is a tile-local
    list scan psum-or'ed over the vertex axis — reduced quantities only,
    as in the dense strategy.  Selections are identical to the dense
    strategies over the same rows (exact integer counts).

    Returns replicated ``(seeds (k,) int32, covered_frac () f32,
    gains (k,) int32)``.
    """
    axes = tuple(theta_axes)
    if method not in ("rebuild", "decrement"):
        raise ValueError(f"unknown method {method}")
    Dv = int(mesh.shape[vertex_axis]) if vertex_axis else 1
    # the vertex-block layout — must match the tiles
    # ShardedStore.index_view emitted, or local ids mean the wrong vertex
    part = partition if partition is not None else vertex_partition(n, Dv)
    n_local = part.block
    starts_arr = _starts_for(mesh, vertex_axis, n, part)

    def local_select(R_local, valid_local, starts=None):
        def counter_of(alive):
            partial = bincount_weighted(
                R_local, alive.astype(jnp.float32)[:, None], n_local)
            return jax.lax.psum(partial, axes)

        def pick(counter, alive):
            if vertex_axis is not None:
                return _vertex_sharded_pick(
                    counter, alive, n, vertex_axis,
                    lambda lv: (R_local == lv).any(axis=1), starts)
            v = jnp.argmax(counter).astype(jnp.int32)
            return v, ((R_local == v).any(axis=1)) & alive

        def dec_of(covered):
            return bincount_weighted(
                R_local, covered.astype(jnp.float32)[:, None], n_local)

        if method == "rebuild":
            def body(i, state):
                alive, seeds, gains = state
                v, covered = pick(counter_of(alive), alive)
                gain = jax.lax.psum(covered.sum(dtype=jnp.int32), axes)
                return (alive & ~covered,
                        seeds.at[i].set(v), gains.at[i].set(gain))

            alive, seeds, gains = jax.lax.fori_loop(
                0, k, body,
                (valid_local, jnp.zeros((k,), jnp.int32),
                 jnp.zeros((k,), jnp.int32)),
            )
        else:
            partial0 = bincount_weighted(
                R_local, valid_local.astype(jnp.float32)[:, None], n_local)

            def body(i, state):
                alive, partial, seeds, gains = state
                v, covered = pick(jax.lax.psum(partial, axes), alive)
                gain = jax.lax.psum(covered.sum(dtype=jnp.int32), axes)
                partial = partial - dec_of(covered)
                return (alive & ~covered, partial,
                        seeds.at[i].set(v), gains.at[i].set(gain))

            alive, _, seeds, gains = jax.lax.fori_loop(
                0, k, body,
                (valid_local, partial0, jnp.zeros((k,), jnp.int32),
                 jnp.zeros((k,), jnp.int32)),
            )
        n_valid = jnp.maximum(
            jax.lax.psum(valid_local.sum(dtype=jnp.float32), axes), 1.0)
        return seeds, gains.sum(dtype=jnp.float32) / n_valid, gains

    if starts_arr is None:
        fn = shard_map(
            local_select, mesh=mesh,
            in_specs=(P(axes, vertex_axis), P(axes)),
            out_specs=(P(), P(), P()),
        )
        return fn(R_idx, valid)
    fn = shard_map(
        local_select, mesh=mesh,
        in_specs=(P(axes, vertex_axis), P(axes), P()),
        out_specs=(P(), P(), P()),
    )
    return fn(R_idx, valid, starts_arr)


# ---------------------------------------------------------------- fused ----

@partial(jax.jit, static_argnames=("n", "k", "method", "codec", "interpret"))
def select_fused(R, valid, n: int, k: int, method: str = "rebuild", *,
                 codec=None, interpret: bool = False):
    """Greedy selection whose per-round reduction runs through the
    `repro.kernels.ops` dispatch (Pallas on TPU, ``interpret=True`` for
    CPU kernel validation, jnp oracle elsewhere) — the fused counterpart
    of `select_dense`/`select_packed`/`select_compressed`, bitwise-equal
    to all of them over the same rows (exact integer counts in f32, and
    the `fused_select` kernel's tie-break equals ``jnp.argmax``).

    ``R`` is the at-rest arena in the layout ``codec`` names: raw
    ``(theta, n) uint8`` bitmaps when ``codec`` is None/bitmap, encoded
    ``(theta, codec.width)`` tiles otherwise — encoded arenas are
    counted with the decode-and-count kernels, so the decoded
    ``(theta, n)`` block never exists.  For bitmap rebuild rounds the
    `fused_select` kernel returns the winning vertex directly and the
    per-round ``(n,)`` counter is never materialized either.
    """
    kind = "bitmap" if codec is None else codec.kind

    def counter_of(alive):
        return kops.arena_count(R, alive, codec=codec, interpret=interpret)

    def member(v):
        if kind == "bitmap":
            return R[:, v] > 0
        return codec.decode_cols(R, v.reshape(1))[:, 0]

    if method == "rebuild":
        def body(i, state):
            alive, seeds, gains = state
            if kind == "bitmap":
                _, v = kops.fused_select(
                    alive.astype(jnp.float32), R, interpret=interpret)
                v = v.astype(jnp.int32)
            else:
                v = jnp.argmax(counter_of(alive)).astype(jnp.int32)
            covered = member(v) & alive
            gain = covered.sum(dtype=jnp.int32)
            return alive & ~covered, seeds.at[i].set(v), gains.at[i].set(gain)

        alive, seeds, gains = jax.lax.fori_loop(
            0, k, body,
            (valid, jnp.zeros((k,), jnp.int32), jnp.zeros((k,), jnp.int32)))
    elif method == "decrement":
        def body(i, state):
            alive, counter, seeds, gains = state
            v = jnp.argmax(counter).astype(jnp.int32)
            covered = member(v) & alive
            gain = covered.sum(dtype=jnp.int32)
            counter = counter - counter_of(covered)
            return (alive & ~covered, counter,
                    seeds.at[i].set(v), gains.at[i].set(gain))

        alive, _, seeds, gains = jax.lax.fori_loop(
            0, k, body,
            (valid, counter_of(valid), jnp.zeros((k,), jnp.int32),
             jnp.zeros((k,), jnp.int32)))
    else:
        raise ValueError(f"unknown method {method}")

    n_valid = jnp.maximum(valid.sum(dtype=jnp.float32), 1.0)
    return seeds, gains.sum(dtype=jnp.float32) / n_valid, gains


def greedy_select(R_or_idx, valid, k: int, *, n: int | None = None,
                  representation: str = "bitmap", method: str = "rebuild"):
    """Unified entry point used by the IMM driver."""
    if representation == "bitmap":
        return select_dense(R_or_idx, valid, k, method)
    if representation == "indices":
        assert n is not None
        return select_sparse(R_or_idx, valid, n, k, method)
    raise ValueError(representation)


# ------------------------------------------------- SelectionStrategy API ----
#
# A strategy is ``fn(view, k, **opts) -> (seeds, covered_frac, gains)`` where
# ``view`` is a ``repro.core.store.StoreView`` (duck-typed: .R, .valid, .n).
# The registry is keyed "<method>-<layout>" with method in
# {rebuild, decrement} and layout in {dense, sparse, sharded}.

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
            partition=None, codec=None, **_):
        if mesh is None:
            raise ValueError("sharded selection needs a mesh")
        return select_dense_sharded(
            mesh, view.R, view.valid, k,
            theta_axes=theta_axes, vertex_axis=vertex_axis, method=method,
            n=view.n, partition=partition, codec=codec)
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


def _fused_dense_strategy(method):
    def run(view, k, *, pallas_interpret=False, **_):
        return select_fused(view.R, view.valid, view.n, k, method,
                            interpret=bool(pallas_interpret))
    return run


def _fused_codec_strategy(method):
    def run(view, k, *, codec=None, pallas_interpret=False, **_):
        if codec is None:
            raise ValueError(
                "fused packed/compressed selection needs the store codec")
        return select_fused(view.R, view.valid, view.n, k, method,
                            codec=codec, interpret=bool(pallas_interpret))
    return run


def _fused_sharded_strategy(method):
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


for _m in ("rebuild", "decrement"):
    register_selection(f"{_m}-dense", _dense_strategy(_m))
    register_selection(f"{_m}-sparse", _sparse_strategy(_m))
    register_selection(f"{_m}-sharded", _sharded_strategy(_m))
    register_selection(f"{_m}-sharded-sparse", _sharded_sparse_strategy(_m))
    # the fused-kernel strategies (PR 10): selection_method="fused-rebuild"
    # / "fused-decrement" routes every layout's reductions through the
    # kernels/ops dispatch.  Index-list layouts have no Pallas kernel —
    # they delegate to the plain strategies so the C4 adaptive switch
    # under a fused method never dead-ends
    register_selection(f"fused-{_m}-dense", _fused_dense_strategy(_m))
    register_selection(f"fused-{_m}-packed", _fused_codec_strategy(_m))
    register_selection(f"fused-{_m}-compressed", _fused_codec_strategy(_m))
    register_selection(f"fused-{_m}-sharded", _fused_sharded_strategy(_m))
    register_selection(f"fused-{_m}-sparse", _sparse_strategy(_m))
    register_selection(f"fused-{_m}-sharded-sparse",
                       _sharded_sparse_strategy(_m))


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
