"""Fused sample->write->count extenders — the PR 10 kernel chain.

The historical `InfluenceEngine.extend` loop makes two device calls per
batch: the bound sampler returns a full ``(B, n)`` row block, then
``store.add_batch`` re-reads that block to encode/write arena tiles and
update the fused counter.  The batch therefore rests in HBM once purely
as a hand-off buffer.  This module inlines the sampler trace into the
same program as the arena commit (`repro.kernels.ops.arena_commit` —
Pallas on TPU, interpret for CPU validation, jnp oracle otherwise), so
the decoded ``(B, n)`` batch only ever exists as a jit temporary and XLA
is free to fuse the frontier loop's final state straight into the
encode + column-count pass.  What crosses the jit boundary is the
batch's *at-rest* arena block with its per-vertex counts already folded
— nothing the store has to re-read, re-encode, or re-count.

The single-device chain is deliberately TWO jits, not one: the
expensive program (sample -> encode -> count) closes over only
fixed-per-cfg shapes, so it compiles once per at-rest kind, while the
arena slice-write lives in a separate module-level jit whose shape
follows the pow2 capacity ladder.  Folding the write into the chain
would retrace the whole sampler at every capacity doubling — the
write's program is `dynamic_update_slice` + one add, so it is the right
side of the boundary to recompile.  The sharded chain splits along the
same line, per tile inside shard_map (`_make_sharded_chain` /
`_sharded_commit_fn`).

Bitwise equivalence with the two-call path is structural, not hoped-for:

  * the engine hands the extender the *bound* (already-jitted) sampler;
    calling a jitted function inside an outer jit inlines the identical
    trace, so the PRNG stream and every sampled bit match the unfused
    path seed-for-seed;
  * the sharded chain computes each tile's encoded block, live-masked
    vertex-axis size psum, and counter partial with the same per-tile
    arithmetic (and specs) as the unfused `_tile_write_body`, so arena
    bytes, sizes, counter partials and counts commit the same values;
  * the single-device chain writes `arena_commit`'s output, whose
    ``stored`` is bitwise-equal to the store codec's ``encode`` and whose
    ``colsum`` is the exact int32 column sum every sampler reports as its
    fused C3 contribution.

``extend_once(key) -> bool`` returns False when the store's *current*
at-rest form is outside the fused chain's coverage (token-compressed
tiles, or a pressure-ladder morph that lands there mid-write) — the
engine then falls back to the historical path with the SAME batch key,
so the sample stream is preserved across the boundary.
"""
from __future__ import annotations

from functools import lru_cache, partial

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.store import (
    BitmapStore, ShardedStore, _psum_if,
)
from repro.compat import shard_map
from jax.sharding import PartitionSpec as P
from repro.kernels import ops as kops

# the at-rest forms arena_commit covers; token-compressed rows fall back
_FUSED_KINDS = ("bitmap", "packed")


def make_fused_extender(store, sample, cfg, *, sampler_name: str):
    """The fused extender for ``(store, bound sampler)``, or None when
    the store kind has no fused chain (IndexStore emits index lists; the
    chain is dense-at-rest only)."""
    interpret = bool(getattr(cfg, "pallas_interpret", False))
    batch = int(cfg.batch)
    if isinstance(store, ShardedStore):
        return _ShardedFused(store, sample, batch,
                             sampler_name=sampler_name)
    from repro.core.pack.stores import CodecStore
    if isinstance(store, (BitmapStore, CodecStore)):
        return _ArenaFused(store, sample, batch, interpret=interpret,
                           sampler_name=sampler_name)
    return None


def _sample_counted(sample, key):
    """``(visited, steps)`` from the bound sampler: ``steps`` is its
    while loop's trip count, or None for a sampler that does not count
    (`repro.core.sampler.TraversalBackend`)."""
    if getattr(sample, "coins_per_step", None) is None:
        return sample(key)[0], None
    visited, _, _, steps = sample(key, with_steps=True)
    return visited, steps


def _work(sample, steps, colsum) -> dict:
    """The batch's device counts the chain returns beside its commit
    (two int32 scalars, dropped by the caller when obs is off): the
    loop's ``steps`` and, for coin models, ``consulted``, the (row,
    edge) pairs the traversal tried: ``colsum . in_degree``, since each
    member of a row fronts once and tries each of its in-edges once."""
    if steps is None:
        return {}
    work = {"steps": steps}
    in_degree = getattr(sample, "in_degree", None)
    if in_degree is not None:
        work["consulted"] = (colsum * in_degree).sum(dtype=jnp.int32)
    return work


def _sample_args(sample, batch: int, work: dict) -> dict:
    """The fused ``sample`` span's arguments: ``sets``, and the device
    counts of `_work` with ``coins = steps x coins_per_step``, formed as
    a Python int at export (a batch's coins can pass 2**31)."""
    args = {"sets": batch, **work}
    if "steps" in work:
        args["coins"] = obs.Deferred(work["steps"], sample.coins_per_step)
    return args


def _make_chain_fn(sample, kind: str, interpret: bool):
    """The fixed-shape half of a single-device fused batch: sample ->
    arena_commit (encode + column count in one kernel pass).  Shapes
    depend only on (batch, n), never on arena capacity, so this — the
    program that contains the whole sampler trace — compiles exactly
    once per at-rest kind.  It also returns the batch's `_work`."""

    @jax.jit
    def chain(key):
        visited, steps = _sample_counted(sample, key)
        visited = visited.astype(jnp.uint8)
        stored, colsum = kops.arena_commit(visited, kind=kind,
                                           interpret=interpret)
        return (stored, visited.sum(axis=1, dtype=jnp.int32), colsum,
                _work(sample, steps, colsum))

    return chain


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _commit_write(R, sizes, counter, stored, batch_sizes, colsum, start):
    """The capacity-shaped half: donated in-place arena writes.  Tiny
    program — recompiling it at each pow2 capacity growth costs what the
    unfused path's `_write_rows` already pays, and being module-level
    its cache is shared by every engine in the process."""
    R = jax.lax.dynamic_update_slice(R, stored, (start, jnp.int32(0)))
    sizes = jax.lax.dynamic_update_slice(sizes, batch_sizes, (start,))
    return R, sizes, counter + colsum


class _ArenaFused:
    """Fused extender over the single-device arenas (`BitmapStore`,
    packed `CodecStore`).  One compiled chain per at-rest kind, cached —
    a pressure-ladder morph to tokens makes `extend_once` decline."""

    def __init__(self, store, sample, batch: int, *, interpret: bool,
                 sampler_name: str):
        self.store = store
        self._sample = sample
        self.batch = batch
        self.interpret = interpret
        self.sampler_name = sampler_name
        self._fns: dict = {}

    def extend_once(self, key) -> bool:
        s = self.store
        if s.representation not in _FUSED_KINDS:
            return False
        B = self.batch
        s._ensure_room(B)
        kind = s.representation
        if kind not in _FUSED_KINDS:
            # the compress ladder just morphed to token rows; the legacy
            # path (same key) handles token widening
            return False
        s._grow_rows(s.count + B)
        fn = self._fns.get(kind)
        if fn is None:
            fn = self._fns[kind] = _make_chain_fn(
                self._sample, kind, self.interpret)
        # chain and commit are separate device calls, so the spans are
        # siblings under the engine's extend — the same topology the
        # unfused sample + add_batch path reports
        with obs.span("sample", tier="engine", sampler=self.sampler_name,
                      fused=True) as sp:
            stored, batch_sizes, colsum, work = fn(key)
            if sp is not None:
                sp.set(**_sample_args(self._sample, B, work))
        with obs.span("store.write", tier="store", kind=kind,
                      fused=True) as sp:
            s.R, s.sizes, s.counter = _commit_write(
                s.R, s.sizes, s.counter, stored, batch_sizes, colsum,
                jnp.int32(s.count))
            if sp is not None:
                # rows and sizes written, the counter read and written
                sp.set(sets=B, bytes=B * s._row_bytes() + 4 * B + 8 * s.n)
        s._note_write(B)
        return True


def _make_sharded_chain(sample, store, batch: int):
    """The fixed-shape half of a meshed fused batch: sample (shard-local
    placement) -> column layout -> per-tile encode + size/counter
    partials, under the same specs and the same per-tile arithmetic as
    the unfused `_tile_write_body` — encode from bit rows, live-masked
    vertex-axis psum for sizes, tile-local counter partial — so every
    committed value is bitwise the unfused one.  No arena operand means
    no recompile when the capacity ladder grows."""
    s = store
    codec, vertex_axis = s._codec_arg, s.vertex_axis
    sp_rows, sp_vec = P(s.theta_axes, s.vertex_axis), P(s.theta_axes)

    def tile(rows, incs):
        stored = rows if codec is None else codec.encode(rows)
        live = jnp.arange(rows.shape[0], dtype=jnp.int32) < incs[0]
        row_sizes = _psum_if(rows.sum(axis=1, dtype=jnp.int32),
                             vertex_axis)
        row_sizes = jnp.where(live, row_sizes, 0)
        counter_d = rows.sum(axis=0, dtype=jnp.int32)[None, :]
        return stored, row_sizes, counter_d

    enc = shard_map(tile, mesh=s.mesh, in_specs=(sp_rows, sp_vec),
                    out_specs=(sp_rows, sp_vec, sp_rows))
    b = -(-batch // s.D)
    pad = b * s.D - batch

    @jax.jit
    def chain(key, incs):
        visited, steps = _sample_counted(sample, key)
        visited = visited.astype(jnp.uint8)
        work = _work(sample, steps, visited.sum(axis=0, dtype=jnp.int32))
        visited = s._layout_cols(visited)
        if pad:
            visited = jnp.concatenate(
                [visited, jnp.zeros((pad, s.n_pad), jnp.uint8)])
        visited = jax.lax.with_sharding_constraint(visited, s._sh_rows)
        return enc(visited, incs) + (work,)

    return chain


@lru_cache(maxsize=None)
def _sharded_commit_fn(mesh, theta_axes, vertex_axis):
    """The capacity-shaped half: donated per-tile arena writes of an
    already-encoded block plus the pre-computed size/counter partials.
    Cached per (mesh, axes) like `_sharded_write_kernels`, so its (tiny)
    per-capacity compiles are shared by every store in the process."""
    sp_rows, sp_vec = P(theta_axes, vertex_axis), P(theta_axes)

    def tile(R, sizes, counter, counts, stored, row_sizes, counter_d,
             incs):
        start = counts[0]
        R = jax.lax.dynamic_update_slice(R, stored, (start, jnp.int32(0)))
        sizes = jax.lax.dynamic_update_slice(sizes, row_sizes, (start,))
        return R, sizes, counter + counter_d, counts + incs

    return jax.jit(
        shard_map(tile, mesh=mesh,
                  in_specs=(sp_rows, sp_vec, sp_rows, sp_vec,
                            sp_rows, sp_vec, sp_rows, sp_vec),
                  out_specs=(sp_rows, sp_vec, sp_rows, sp_vec)),
        donate_argnums=(0, 1, 2, 3))


class _ShardedFused:
    """Fused extender over `ShardedStore` bitmap/packed tiles.  Chains
    are cached per tile codec (``_codec_arg``), so a ladder morph from
    bitmap to packed tiles recompiles once and keeps fusing; a morph to
    token tiles declines to the legacy path."""

    def __init__(self, store, sample, batch: int, *, sampler_name: str):
        self.store = store
        self._sample = sample
        self.batch = batch
        self.sampler_name = sampler_name
        b = -(-batch // store.D)
        self._incs_np = np.clip(
            batch - np.arange(store.D) * b, 0, b).astype(np.int32)
        self._b = b
        self._fns: dict = {}

    def extend_once(self, key) -> bool:
        s = self.store
        if s.codec.kind not in _FUSED_KINDS:
            return False
        s._ensure_room(self._b)
        if s.codec.kind not in _FUSED_KINDS:
            return False
        s._grow_rows(self._b)
        fn = self._fns.get(s._codec_arg)
        if fn is None:
            fn = self._fns[s._codec_arg] = _make_sharded_chain(
                self._sample, s, self.batch)
        commit = _sharded_commit_fn(s.mesh, s.theta_axes, s.vertex_axis)
        incs = jax.device_put(jnp.asarray(self._incs_np), s._sh_vec)
        with obs.span("sample", tier="engine", sampler=self.sampler_name,
                      fused=True) as sp:
            stored, row_sizes, counter_d, work = fn(key, incs)
            if sp is not None:
                sp.set(**_sample_args(self._sample, self.batch, work))
                # the edge window each step walks against the edges owned
                sp.set(**{k: getattr(self._sample, k)
                          for k in ("walked", "owned")
                          if hasattr(self._sample, k)})
        with obs.span("store.write", tier="store", kind=s.codec.kind,
                      fused=True) as sp:
            s.R, s.sizes, s._counter, s._counts = commit(
                s.R, s.sizes, s._counter, s._counts, stored,
                row_sizes, counter_d, incs)
            s._counts_host += self._incs_np
            if sp is not None:
                # every shard's b rows and sizes written (padding rows
                # too), the counter partials read and written
                rows = self._b * s.D
                sp.set(sets=self.batch,
                       bytes=rows * s._row_bytes() + 4 * rows
                       + 8 * int(np.prod(s._counter.shape)))
        s._note_write(self.batch)
        return True
