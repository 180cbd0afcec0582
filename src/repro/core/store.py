"""Persistent RRR-set arenas — the resident store behind `InfluenceEngine`.

The paper's C1/C3/C4/C5 optimizations all hinge on *where the sampled RRR
sets live*: fused counting writes into a store-owned counter, the adaptive
representation is a property of the store, the NUMA/device partitioning of
the sets is a property of the store, and selection reads the store without
reshaping it.  This module makes that explicit:

  * ``RRRStore``   — the protocol every backend implements: in-place
    ``add_batch``, a shape-stable ``view()`` for selection, fused per-node
    ``counter`` (C3), per-set ``sizes``, batched membership queries
    (``hits``), and ``state()``/``from_state`` for snapshots.
  * ``BitmapStore`` — single-device ``(capacity, n) uint8`` bitmap arena.
    Capacity is a power of two grown by amortized doubling; batches are
    written in place with a donated ``dynamic_update_slice`` so the hot
    loop never re-concats O(theta) rows and jit recompilations are bounded
    by O(log theta) distinct arena shapes.  Converts to index lists lazily
    (C4) via a version-keyed cache.
  * ``IndexStore``  — ``(capacity, L) int32`` index-list arena (sentinel
    ``n``), for regimes where sets are sparse from the start (LT walks,
    huge graphs); widens ``L`` by power-of-two steps as larger sets arrive.
  * ``ShardedStore`` — the paper's C1 partitioning end-to-end: a bitmap
    arena sharded across a ``jax.sharding.Mesh`` — the theta axis over
    ``theta_axes`` and, on 2D meshes, the vertex axis over
    ``vertex_axis``.  Every device owns a ``(cap_local, n_local)`` tile
    (``n_local = ceil(n / Dv)``); batch writes, fused counting, the row
    lifecycle and per-shard growth all happen device-locally inside
    donated ``shard_map`` kernels, so the full ``(theta, n)`` arena
    never exists on any single device — theta scales with the theta axis
    and graph size with the vertex axis (docs/sharding.md).

All backends preserve exact equivalence with the historical pad-to-pow2
selection inputs: padding rows are all-zero (bitmap) / all-sentinel
(indices) and masked by ``view().valid``.  For ``ShardedStore``, row
*placement* is a layout detail, not a semantic one — selection, ``hits``
and the global counter are permutation-invariant over rows (every
reduction is an exact integer sum), so results are seed-for-seed
identical to a ``BitmapStore`` fed the same sample stream, on any mesh.

Streaming (``repro.stream``) adds a **row lifecycle** on top of the
grow-only arena: every filled row carries a ``live`` bit, and
``view().valid`` is ``filled & live`` — a killed (stale or evicted) row
drops out of selection, ``hits`` and the fused counter *immediately*,
with no rebuild.  Three primitives drive it, all in place:

  * ``kill_rows(mask)``    — mark rows dead and subtract their fused-
    counter contribution (invalidation and eviction share this path);
  * ``replace_rows(i, b)`` — overwrite dead slots with freshly sampled
    rows and revive them (the streaming refresh write);
  * ``compact()``          — rewrite live rows to the arena head (per
    shard for `ShardedStore`), reclaiming dead slots; returns an
    old-slot -> new-slot remap so callers tracking row provenance can
    follow the move.

`StorePressurePolicy` bounds resident memory (``max_rows`` /
``max_bytes``): ``add_batch`` under a policy first compacts (dead rows
are the first victims — staleness-first), then evicts the oldest live
rows, so arena capacity never exceeds the cap on an indefinite stream.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Protocol, runtime_checkable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.compat import shard_map
from repro.core.adaptive import bitmap_to_indices
from repro.graphs.partition import VertexPartition, vertex_partition
from repro.kernels import ops as kops

MIN_CAPACITY = 16     # matches the historical pad floor (1 << 4)
MIN_INDEX_PAD = 4     # matches the historical l_pad floor (1 << 2)


def next_pow2(x: int, floor: int = MIN_CAPACITY) -> int:
    """Smallest power of two >= max(x, floor)."""
    cap = max(int(floor), 1)
    while cap < x:
        cap <<= 1
    return cap


@dataclasses.dataclass(frozen=True)
class StorePressurePolicy:
    """Bounded-memory contract for an indefinite stream of batches.

    ``max_rows`` caps the arena's row capacity directly; ``max_bytes``
    caps it through the backend's *physical* bytes-per-row (``n`` for
    bitmaps, ``4 * l_pad`` for index lists, ``ceil(n/8)`` packed,
    ``4 * s_pad`` compressed); when both are set the tighter one wins.
    Victim order under pressure is **staleness-first**: dead
    (stale/invalidated) rows are reclaimed by compaction before any live
    row is touched, then the *oldest* live rows are evicted FIFO — the
    lowest-information residents under a growing theta schedule (HBMax's
    observation: early small-theta samples are the cheapest to drop).

    ``ladder`` makes the eviction-vs-compression tradeoff explicit
    (IMPack): an ordered tuple of codec kinds (subset of ``("packed",
    "compressed")``) the arena may morph *down* through when a write
    would not fit — compress-before-evict.  Each step shrinks
    bytes-per-row, so a ``max_bytes`` cap admits more rows; only when
    the ladder is exhausted do live rows get evicted.  Backends that
    cannot morph their layout (`BitmapStore`, `IndexStore`) ignore the
    ladder; `repro.core.pack.CodecStore` and codec-bearing
    `ShardedStore` arenas honor it.
    """
    max_rows: int | None = None
    max_bytes: int | None = None
    ladder: tuple = ()

    def row_cap(self, row_bytes: int) -> int | None:
        """Effective row capacity for a backend storing ``row_bytes`` per
        row, or None when the policy is unbounded."""
        caps = []
        if self.max_rows is not None:
            caps.append(int(self.max_rows))
        if self.max_bytes is not None:
            caps.append(int(self.max_bytes) // max(int(row_bytes), 1))
        if not caps:
            return None
        cap = min(caps)
        if cap < 1:
            raise ValueError(
                f"StorePressurePolicy resolves to a row cap of {cap} "
                f"(row_bytes={row_bytes}); the cap must hold >= 1 row")
        return cap


_LADDER_RANK = {"bitmap": 0, "packed": 1, "compressed": 2}


def _ladder_next(current_kind: str, ladder) -> str | None:
    """Next codec kind a pressure ladder may morph ``current_kind`` down
    to, or None when the ladder is exhausted.  Only strictly-denser
    kinds qualify — a ladder can never decompress an arena."""
    rank = _LADDER_RANK.get(current_kind, 0)
    for kind in ladder:
        if _LADDER_RANK.get(kind, -1) > rank:
            return kind
    return None


@dataclasses.dataclass(frozen=True)
class StoreView:
    """Read-only picture of an arena handed to a `SelectionStrategy`.

    ``R`` is ``(capacity, n) uint8`` bitmaps when ``representation ==
    "bitmap"`` and ``(capacity, L) int32`` sentinel-padded index lists when
    ``representation == "indices"``.  For single-device stores, rows at
    index >= ``count`` are padding and ``valid`` is the prefix mask
    ``arange(capacity) < count``.  For `ShardedStore` views, ``R`` is the
    *sharded* global arena (``P(theta_axes, vertex_axis)``; column count
    ``n_pad >= n`` on 2D meshes — pad columns are all-zero, and index
    views hold *local* vertex ids per tile), valid rows are a per-shard
    prefix rather than a global one, and ``valid`` (sharded
    ``P(theta_axes)``) masks exactly the rows each shard has filled —
    consumers must always mask by ``valid`` instead of assuming
    contiguity.

    Views alias the live arena buffer, which `add_batch` donates to its
    in-place writer — a view is only safe to read until the store's next
    write (on accelerator backends the donated buffer is literally
    deleted).  Consume a view before mutating the store; re-call ``view()``
    after.
    """
    representation: str
    R: jnp.ndarray
    valid: jnp.ndarray
    n: int
    count: int


def _coverage_stats(sizes, count: int, n: int) -> tuple[float, int]:
    """(avg fractional set coverage, max set size) from a sizes array —
    padding entries are zero, so sums/maxes ignore them."""
    sizes = np.asarray(sizes)
    avg_cov = float(sizes.sum()) / max(count, 1) / n
    return avg_cov, max(int(sizes.max()) if sizes.size else 1, 1)


@jax.jit
def _valid_rows(live, count):
    """The filled rows still live, ``arange(capacity) < count`` and the
    live bits, as one program (a select's host time pays per dispatch)."""
    return (jnp.arange(live.shape[0]) < count) & live


@partial(jax.jit, donate_argnums=(0,))
def _write_rows(arena, rows, start):
    """In-place (donated) row-block write at dynamic offset ``start``."""
    start_idx = (start,) + (jnp.int32(0),) * (arena.ndim - 1)
    return jax.lax.dynamic_update_slice(arena, rows, start_idx)


@partial(jax.jit, donate_argnums=(0, 1))
def _compact_rows(R, sizes, keep, fill):
    """Stable-partition live rows to the arena head, dead slots to
    ``fill`` padding.  The sort key ``(~keep) * cap + iota`` is unique, so
    the permutation is deterministic and order-preserving among kept rows
    (oldest rows stay first — the FIFO order eviction relies on)."""
    cap = keep.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    perm = jnp.argsort(jnp.where(keep, 0, 1) * cap + iota)
    newvalid = iota < keep.sum(dtype=jnp.int32)
    R = jnp.where(newvalid[:, None], R[perm], fill)
    sizes = jnp.where(newvalid, sizes[perm], 0)
    return R, sizes


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _replace_rows_kernel(R, sizes, live, counter, idx, rows, row_sizes,
                         contrib):
    """Scatter fresh ``rows`` into dead slots ``idx``, revive their live
    bits, and add the replacement contribution to the fused counter.
    ``idx`` entries of -1 are padding (callers pad the target count to a
    power of two so jit retraces stay O(log capacity)) — they scatter
    out-of-bounds and drop; their ``contrib`` share is pre-masked."""
    tgt = jnp.where(idx >= 0, idx, R.shape[0])
    R = R.at[tgt].set(rows, mode="drop")
    sizes = sizes.at[tgt].set(row_sizes, mode="drop")
    live = live.at[tgt].set(True, mode="drop")
    return R, sizes, live, counter + contrib


def _restore_live(store, st) -> None:
    """Re-apply a snapshot's live bits (absent in pre-streaming
    snapshots, where every filled row is live)."""
    if "live" in st:
        live = np.asarray(st["live"]).astype(bool)
        store.live = jnp.asarray(live)
        store.dead = int(store.count - live[:store.count].sum())


@jax.jit
def _bitmap_hits(R, valid, S):
    """Fraction of valid sets hit by each seed row. S: (Q, L) int32."""
    memb = R[:, S.reshape(-1)].reshape((R.shape[0],) + S.shape) > 0
    hit = memb.any(axis=2) & valid[:, None]
    n_valid = jnp.maximum(valid.sum(dtype=jnp.float32), 1.0)
    return hit.sum(axis=0).astype(jnp.float32) / n_valid


@jax.jit
def _index_hits(R_idx, valid, S):
    """Index-list membership version of `_bitmap_hits` (lax.map bounds the
    (capacity, L, Lq) broadcast to one query at a time)."""
    n_valid = jnp.maximum(valid.sum(dtype=jnp.float32), 1.0)

    def one(s):
        memb = (R_idx[:, :, None] == s[None, None, :]).any(axis=(1, 2))
        return (memb & valid).sum(dtype=jnp.int32)

    hits = jax.lax.map(one, S)
    return hits.astype(jnp.float32) / n_valid


@runtime_checkable
class RRRStore(Protocol):
    """Protocol for RRR-set stores consumed by `InfluenceEngine`.

    ``add_batch(visited, counter=None)`` takes ``(B, n) uint8`` bitmaps and
    appends them in place (implementations donate their arena buffer — do
    not hold references to a previous ``view()`` across a write),
    returning the slot index each row landed in (streaming provenance).
    ``counter`` is the sampler's fused ``(n,) int32`` batch contribution;
    backends may recompute it locally instead (``ShardedStore`` does, so
    the count stays shard-local).  ``view()`` returns a `StoreView` whose
    arrays alias live buffers; ``hits(S)`` answers ``(Q, L) int32`` seed-
    set membership queries as per-query covered fractions ``(Q,) f32``;
    ``state()`` returns a host pytree for `checkpoint.store`.  Streaming
    consumers additionally use the row lifecycle (``kill_rows`` /
    ``replace_rows`` / ``compact``, ``live_count``, ``row_cap``) — see
    the module docstring.
    """
    representation: str
    n: int
    count: int
    capacity: int
    version: int
    counter: jnp.ndarray
    sizes: jnp.ndarray

    def add_batch(self, visited, counter=None) -> np.ndarray: ...
    def view(self) -> StoreView: ...
    def hits(self, S) -> jnp.ndarray: ...
    def coverage_stats(self) -> tuple[float, int]: ...
    def state(self) -> dict: ...


class _ArenaBase:
    """Shared arena bookkeeping: pow2 capacity, doubling, fused counter,
    and the streaming row lifecycle (live bits, kill/replace/compact,
    pressure-policy eviction)."""

    def __init__(self, n: int, *, capacity: int = MIN_CAPACITY,
                 policy: StorePressurePolicy | None = None):
        self.n = int(n)
        self.capacity = next_pow2(capacity)
        self.count = 0
        self.dead = 0           # filled rows whose live bit is cleared
        self.version = 0
        self.policy = policy
        self.track_remaps = False   # StreamEngine opts in to remap logging
        self._remaps: list[np.ndarray] = []
        self.sizes = jnp.zeros((self.capacity,), jnp.int32)
        self.counter = jnp.zeros((self.n,), jnp.int32)
        self.live = jnp.ones((self.capacity,), jnp.bool_)

    def _grow_rows(self, need: int):
        new_cap = next_pow2(need, self.capacity)
        cap = self.row_cap
        if cap is not None:
            # capacity is clamped to the policy cap (possibly non-pow2);
            # _ensure_room already guaranteed need <= cap
            new_cap = min(new_cap, max(cap, self.capacity))
        if new_cap == self.capacity:
            return
        old = self.capacity
        # bytes from shapes: the new arena and sizes are filled whole and
        # the old rows read and written into them; live is concatenated
        with obs.span("store.grow", tier="store", old_capacity=old,
                      new_capacity=new_cap,
                      bytes=(new_cap + 2 * old) * (self._row_bytes() + 4)
                      + new_cap + old):
            self._realloc(new_cap)
            sizes = jnp.zeros((new_cap,), jnp.int32)
            self.sizes = _write_rows(sizes, self.sizes, jnp.int32(0))
            self.live = jnp.concatenate(
                [self.live, jnp.ones((new_cap - old,), jnp.bool_)])
            self.capacity = new_cap

    def _finish_add(self, batch_sizes, counter):
        B = batch_sizes.shape[0]
        self.sizes = _write_rows(self.sizes, batch_sizes, jnp.int32(self.count))
        self.counter = self.counter + counter
        self._note_write(int(B))

    def _note_write(self, B: int):
        """Host-side bookkeeping after ``B`` rows landed in the arena —
        shared by `add_batch` and the fused sample->write->count path
        (`repro.core.fused`), which commits rows without ever staging a
        separate batch array."""
        self.count += int(B)
        self.version += 1
        if obs.enabled():
            # host arithmetic only — shapes the store already tracks,
            # never a device read
            obs.counter("store.rows_written").add(int(B))
            obs.gauge("store.occupancy").set(self.count / self.capacity)
            # physical at-rest bytes (_row_bytes is per-backend: packed
            # and compressed arenas report their encoded width, not the
            # logical uint8 bitmap width)
            arena = self.capacity * self._row_bytes()
            obs.gauge("store.arena_bytes").set(arena)
            obs.gauge("store.bytes_per_device").set(arena)
            obs.gauge("store.compress_ratio").set(
                self.capacity * self.n / max(arena, 1))

    def _valid(self):
        return _valid_rows(self.live, self.count)

    def coverage_stats(self) -> tuple[float, int]:
        """(avg fractional set coverage, max set size) over *live* sets
        (killed rows have their sizes zeroed)."""
        return _coverage_stats(self.sizes, self.live_count, self.n)

    # ---------------------------------------------------- row lifecycle ----

    @property
    def live_count(self) -> int:
        """Filled rows that are still live (the streaming effective theta)."""
        return self.count - self.dead

    @property
    def row_cap(self) -> int | None:
        """Policy row capacity for this backend, or None (unbounded)."""
        if self.policy is None:
            return None
        return self.policy.row_cap(self._row_bytes())

    def live_mask(self) -> jnp.ndarray:
        """``(capacity,) bool`` live bits (True for unfilled slots too —
        mask by the fill prefix, as ``view().valid`` does)."""
        return self.live

    def drain_remaps(self) -> list[np.ndarray]:
        """Pop the slot remaps recorded since the last drain (only
        populated while ``track_remaps`` is set).  Each entry maps
        old slot -> new slot, with -1 for reclaimed slots; apply them in
        order to follow rows across compactions."""
        out, self._remaps = self._remaps, []
        return out

    def kill_rows(self, dead) -> int:
        """Mark rows dead (stale or evicted): they leave ``view().valid``,
        ``hits`` and the fused counter immediately; their slots are
        reclaimed by the next `compact`.  ``dead`` is a ``(capacity,)``
        bool mask (host or device); bits outside the filled-and-live set
        are ignored.  Returns the number of newly dead rows."""
        dead = jnp.asarray(dead) & self._valid()
        k = int(np.asarray(dead.sum()))
        if k == 0:
            return 0
        self.counter = self.counter - self._row_contrib(dead)
        self.sizes = jnp.where(dead, 0, self.sizes)
        self.live = self.live & ~dead
        self.dead += k
        self.version += 1
        obs.counter("store.rows_killed").add(k)
        return k

    def replace_rows(self, idx, rows) -> None:
        """Overwrite dead slots ``idx (K,) int`` with fresh ``rows (K, n)
        uint8`` bitmaps and revive them — the streaming refresh write.
        Targets must be filled, dead slots (enforced); ``idx`` entries of
        -1 are padding and ignored (callers may pre-pad; this method also
        pads the batch to a power of two to bound jit retraces)."""
        idx = np.asarray(idx, np.int64)
        real = idx >= 0
        k = int(real.sum())
        if k == 0:
            return
        live_host = np.asarray(self.live)
        if (idx[real] >= self.count).any() or live_host[idx[real]].any():
            raise ValueError(
                "replace_rows targets must be filled, dead slots "
                "(kill_rows them first)")
        with obs.span("store.write", tier="store", kind="replace"):
            rows = jnp.asarray(rows).astype(jnp.uint8)
            pad = next_pow2(idx.shape[0], 1) - idx.shape[0]
            if pad:
                idx = np.concatenate([idx, np.full(pad, -1, np.int64)])
                rows = jnp.concatenate(
                    [rows, jnp.zeros((pad, rows.shape[1]), jnp.uint8)])
            mask = jnp.asarray(idx >= 0)
            rows = rows * mask[:, None].astype(jnp.uint8)  # zero pad rows
            row_sizes = rows.sum(axis=1, dtype=jnp.int32)
            stored = self._rows_for_storage(rows)
            self.R, self.sizes, self.live, self.counter = \
                _replace_rows_kernel(
                    self.R, self.sizes, self.live, self.counter,
                    jnp.asarray(idx, jnp.int32), stored, row_sizes,
                    rows.sum(axis=0, dtype=jnp.int32))
            self.dead -= k
            self.version += 1
        obs.counter("store.rows_replaced").add(k)

    def compact(self) -> np.ndarray | None:
        """Rewrite live rows to the arena head in place, reclaiming dead
        slots.  Returns the old->new slot remap (-1 for reclaimed slots),
        or None when there was nothing to reclaim."""
        if self.dead == 0:
            return None
        keep = np.asarray(self._valid())
        self.R, self.sizes = _compact_rows(
            self.R, self.sizes, jnp.asarray(keep), self._fill_value())
        remap = np.full(self.capacity, -1, np.int64)
        remap[keep] = np.arange(int(keep.sum()))
        self.count = int(keep.sum())
        self.dead = 0
        self.live = jnp.ones((self.capacity,), jnp.bool_)
        self.version += 1
        obs.counter("store.compactions").add(1)
        if self.track_remaps:
            self._remaps.append(remap)
        return remap

    def _compress_step(self) -> bool:
        """Morph the arena one step down the policy ladder (see
        `StorePressurePolicy.ladder`); returns True when a step was
        taken.  Backends with a fixed layout cannot morph."""
        return False

    def _ensure_room(self, incoming: int):
        """Pressure-policy enforcement before a batch write, in
        compress-before-evict order: reclaim dead slots first
        (staleness-first victim order), then walk the codec ladder —
        each step shrinks bytes-per-row, so a ``max_bytes`` cap admits
        more rows — and only when the ladder is exhausted evict the
        oldest live rows FIFO until ``incoming`` rows fit."""
        cap = self.row_cap
        if cap is None:
            return
        if self.count + incoming > cap and self.dead:
            self.compact()
        while self.count + incoming > cap and self._compress_step():
            cap = self.row_cap
        if incoming > cap:
            raise ValueError(
                f"batch of {incoming} rows exceeds the policy row cap "
                f"of {cap}")
        if self.count + incoming <= cap:
            return
        self.compact()
        over = self.count + incoming - cap
        if over > 0:
            evicted = self.kill_rows(jnp.arange(self.capacity) < over)
            obs.counter("store.rows_evicted").add(evicted)
            self.compact()

    def _base_state(self) -> dict:
        return {
            "n": np.int64(self.n),
            "count": np.int64(self.count),
            "sizes": np.asarray(self.sizes),
            "counter": np.asarray(self.counter),
            "live": np.asarray(self.live),
        }


class BitmapStore(_ArenaBase):
    """Dense single-device bitmap arena: ``(capacity, n) uint8``,
    zero-padded rows, unsharded (replicated from the mesh's point of
    view).  Use `ShardedStore` when theta must scale past one device."""

    representation = "bitmap"

    def __init__(self, n: int, *, capacity: int = MIN_CAPACITY,
                 policy: StorePressurePolicy | None = None):
        super().__init__(n, capacity=capacity, policy=policy)
        self.R = jnp.zeros((self.capacity, self.n), jnp.uint8)
        self._idx_cache = None      # (version, l_pad) -> R_idx

    def _realloc(self, new_cap: int):
        R = jnp.zeros((new_cap, self.n), jnp.uint8)
        self.R = _write_rows(R, self.R, jnp.int32(0))

    def _row_bytes(self) -> int:
        return self.n

    def _fill_value(self):
        return jnp.uint8(0)

    def _rows_for_storage(self, rows):
        return rows

    def _row_contrib(self, mask):
        """Fused-counter contribution of the masked rows (exact: counts
        fit f32 integers), read tile-wise by the counting kernel."""
        return kops.arena_count(self.R, mask).astype(jnp.int32)

    def add_batch(self, visited, counter=None) -> np.ndarray:
        """Append ``visited (B, n) uint8`` rows in place.

        The arena buffer is donated to the writer — any outstanding
        ``view()`` of this store is invalidated by this call.  ``counter``
        is the sampler's fused ``(n,) int32`` contribution (computed here
        when absent).  Returns the slot indices the batch rows landed in
        (streaming consumers track row provenance with them).  Under a
        `StorePressurePolicy` the write may first compact and evict (see
        ``_ensure_room``).
        """
        with obs.span("store.write", tier="store", kind="bitmap"):
            visited = jnp.asarray(visited).astype(jnp.uint8)
            B = int(visited.shape[0])
            self._ensure_room(B)
            self._grow_rows(self.count + B)
            if counter is None:
                counter = visited.sum(axis=0, dtype=jnp.int32)
            slots = np.arange(self.count, self.count + B, dtype=np.int64)
            self.R = _write_rows(self.R, visited, jnp.int32(self.count))
            self._finish_add(visited.sum(axis=1, dtype=jnp.int32), counter)
        return slots

    def view(self) -> StoreView:
        """Aliasing `StoreView` of the live ``(capacity, n)`` arena with
        the prefix mask ``arange(capacity) < count``; read it before the
        next ``add_batch`` (which donates the buffer)."""
        return StoreView("bitmap", self.R, self._valid(), self.n, self.count)

    def index_view(self, l_pad: int) -> StoreView:
        """Lazy C4 conversion; cached until the arena next changes."""
        key = (self.version, int(l_pad))
        if self._idx_cache is None or self._idx_cache[0] != key:
            self._idx_cache = (key, bitmap_to_indices(self.R, int(l_pad)))
        return StoreView("indices", self._idx_cache[1], self._valid(),
                         self.n, self.count)

    def hits(self, S) -> jnp.ndarray:
        """Covered fraction per query: ``S (Q, L) int32`` -> ``(Q,) f32``."""
        with obs.span("count", tier="store", kind="bitmap"):
            return _bitmap_hits(self.R, self._valid(),
                                jnp.asarray(S, jnp.int32))

    def state(self) -> dict:
        """Host snapshot pytree: full ``(capacity, n)`` arena plus
        counters (kind tag ``"bitmap"``)."""
        st = self._base_state()
        st["kind"] = np.asarray("bitmap")
        st["R"] = np.asarray(self.R)
        return st

    @classmethod
    def from_state(cls, st) -> "BitmapStore":
        store = cls(int(st["n"]), capacity=st["R"].shape[0])
        store.R = jnp.asarray(st["R"], jnp.uint8)
        store.sizes = jnp.asarray(st["sizes"], jnp.int32)
        store.counter = jnp.asarray(st["counter"], jnp.int32)
        store.count = int(st["count"])
        _restore_live(store, st)
        return store

    @classmethod
    def from_rows(cls, rows, n: int) -> "BitmapStore":
        """Build a store holding exactly ``rows (count, n) uint8`` — the
        cross-layout restore path (e.g. a `ShardedStore` snapshot opened
        without a mesh).  ``_restore_slots`` records where each input row
        landed (snapshot-row -> slot), so provenance trackers
        (`repro.stream.StreamEngine`) can follow rows through a restore."""
        store = cls(int(n), capacity=max(int(rows.shape[0]), MIN_CAPACITY))
        if rows.shape[0]:
            store._restore_slots = store.add_batch(jnp.asarray(rows, jnp.uint8))
        else:
            store._restore_slots = np.zeros((0,), np.int64)
        return store


class IndexStore(_ArenaBase):
    """Sparse index-list arena: ``(capacity, L) int32`` with sentinel ``n``.

    ``L`` widens by power-of-two steps when a batch contains a larger set
    (the widened columns backfill with the sentinel, so old rows keep their
    meaning).  Incoming bitmap batches are converted on write — after that
    the bitmaps are dropped, so resident memory is O(theta * L) not
    O(theta * n).
    """

    representation = "indices"

    def __init__(self, n: int, *, capacity: int = MIN_CAPACITY,
                 l_pad: int = MIN_INDEX_PAD,
                 policy: StorePressurePolicy | None = None):
        super().__init__(n, capacity=capacity, policy=policy)
        self.l_pad = next_pow2(l_pad, MIN_INDEX_PAD)
        self.R = jnp.full((self.capacity, self.l_pad), self.n, jnp.int32)

    def _realloc(self, new_cap: int):
        R = jnp.full((new_cap, self.l_pad), self.n, jnp.int32)
        self.R = _write_rows(R, self.R, jnp.int32(0))

    def _widen(self, l_need: int):
        new_l = next_pow2(l_need, self.l_pad)
        if new_l == self.l_pad:
            return
        pad = jnp.full((self.capacity, new_l - self.l_pad), self.n, jnp.int32)
        self.R = jnp.concatenate([self.R, pad], axis=1)
        self.l_pad = new_l

    def _row_bytes(self) -> int:
        return 4 * self.l_pad

    def _fill_value(self):
        return jnp.int32(self.n)

    def _rows_for_storage(self, rows):
        self._widen(int(rows.sum(axis=1).max()))
        return bitmap_to_indices(rows, self.l_pad)

    def _row_contrib(self, mask):
        w = jnp.broadcast_to(mask[:, None], self.R.shape)
        return (jnp.zeros((self.n,), jnp.float32)
                .at[self.R.reshape(-1)]
                .add(w.reshape(-1).astype(jnp.float32), mode="drop")
                .astype(jnp.int32))

    def add_batch(self, visited, counter=None) -> np.ndarray:
        with obs.span("store.write", tier="store", kind="indices"):
            visited = jnp.asarray(visited).astype(jnp.uint8)
            B = int(visited.shape[0])
            batch_sizes = visited.sum(axis=1, dtype=jnp.int32)
            self._widen(int(batch_sizes.max()))
            self._ensure_room(B)
            self._grow_rows(self.count + B)
            if counter is None:
                counter = visited.sum(axis=0, dtype=jnp.int32)
            rows = bitmap_to_indices(visited, self.l_pad)
            slots = np.arange(self.count, self.count + B, dtype=np.int64)
            self.R = _write_rows(self.R, rows, jnp.int32(self.count))
            self._finish_add(batch_sizes, counter)
        return slots

    def add_index_batch(self, rows, counter=None) -> np.ndarray:
        """Append pre-converted index rows ``(B, L) int32`` (ascending,
        sentinel >= n) — the native-emission write path (C4 routed
        per-backend: a sparse-backend sampler emits lists directly via
        ``emit_l`` and no ``(B, n)`` bitmap ever materializes between the
        sampler and the arena).  ``counter`` is the sampler's fused
        ``(n,) int32`` contribution (recomputed by scatter when absent);
        the arena widens to ``L`` if needed and narrower rows backfill
        with the sentinel.  Returns the landing slots, like `add_batch`.
        """
        with obs.span("store.write", tier="store", kind="indices"):
            rows = jnp.asarray(rows, jnp.int32)
            B, L = int(rows.shape[0]), int(rows.shape[1])
            batch_sizes = (rows < self.n).sum(axis=1, dtype=jnp.int32)
            self._widen(L)
            if L < self.l_pad:
                rows = jnp.concatenate(
                    [rows, jnp.full((B, self.l_pad - L), self.n, jnp.int32)],
                    axis=1)
            # normalize any emitter sentinel (>= n) to the store's (== n)
            rows = jnp.where(rows < self.n, rows, self.n)
            self._ensure_room(B)
            self._grow_rows(self.count + B)
            if counter is None:
                counter = (jnp.zeros((self.n,), jnp.int32)
                           .at[rows.reshape(-1)].add(1, mode="drop"))
            slots = np.arange(self.count, self.count + B, dtype=np.int64)
            self.R = _write_rows(self.R, rows, jnp.int32(self.count))
            self._finish_add(batch_sizes, counter)
        return slots

    def view(self) -> StoreView:
        return StoreView("indices", self.R, self._valid(), self.n, self.count)

    def hits(self, S) -> jnp.ndarray:
        with obs.span("count", tier="store", kind="indices"):
            return _index_hits(self.R, self._valid(),
                               jnp.asarray(S, jnp.int32))

    def state(self) -> dict:
        st = self._base_state()
        st["kind"] = np.asarray("indices")
        st["R"] = np.asarray(self.R)
        return st

    @classmethod
    def from_state(cls, st) -> "IndexStore":
        store = cls(int(st["n"]), capacity=st["R"].shape[0],
                    l_pad=st["R"].shape[1])
        store.R = jnp.asarray(st["R"], jnp.int32)
        store.sizes = jnp.asarray(st["sizes"], jnp.int32)
        store.counter = jnp.asarray(st["counter"], jnp.int32)
        store.count = int(st["count"])
        _restore_live(store, st)
        return store


# ------------------------------------------------------- sharded (C1) ----


@functools.lru_cache(maxsize=None)
def _sharded_fill(shape, dtype, sharding, value):
    """The program that allocates a ``value``-filled array *born
    sharded* (under jit with ``out_shardings``, so the full logical array
    is never materialized on a single device); cached, so that a store
    emptied or built again at the same shapes compiles nothing."""
    return jax.jit(partial(jnp.full, shape, value, dtype),
                   out_shardings=sharding)


def _sharded_zeros(shape, dtype, sharding):
    """Zeros born sharded (`_sharded_fill`)."""
    return _sharded_fill(tuple(shape), dtype, sharding, 0)()


def _sharded_ones(shape, dtype, sharding):
    """Ones born sharded (`_sharded_fill`)."""
    return _sharded_fill(tuple(shape), dtype, sharding, 1)()


def _psum_if(x, axis):
    """``psum`` over a mesh axis when one is given (the vertex axis is
    None on 1D meshes, where every per-row reduction is already whole)."""
    return x if axis is None else jax.lax.psum(x, axis)


def _tile_write_body(codec, vertex_axis):
    """The per-tile arena write body (the function `shard_map` runs on
    every (theta-shard, vertex-shard) tile): encode + write the batch
    block at the shard's row offset, fuse the size/counter updates, and
    advance the shard count.  Shared verbatim between the unfused
    `_sharded_write_kernels` path and the fused sample->write->count
    chain (`repro.core.fused`), so both compile the identical trace."""

    def write(R, sizes, counter, counts, rows, incs):
        start = counts[0]
        stored = rows if codec is None else codec.encode(rows)
        R = jax.lax.dynamic_update_slice(R, stored, (start, jnp.int32(0)))
        live = jnp.arange(rows.shape[0], dtype=jnp.int32) < incs[0]
        row_sizes = _psum_if(rows.sum(axis=1, dtype=jnp.int32), vertex_axis)
        row_sizes = jnp.where(live, row_sizes, 0)
        sizes = jax.lax.dynamic_update_slice(sizes, row_sizes, (start,))
        counter = counter + rows.sum(axis=0, dtype=jnp.int32)[None, :]
        return R, sizes, counter, counts + incs

    return write


@functools.lru_cache(maxsize=None)
def _sharded_write_kernels(mesh, theta_axes, vertex_axis, codec=None):
    """Compiled per-(mesh, axes) store kernels, shared across stores.

    Returns ``(write, valid)``:
      * ``write(R, sizes, counter, counts, rows, incs)`` — every
        (theta-shard, vertex-shard) tile writes its ``(b, n_local)`` block
        of the batch into its local arena tile at its theta shard's row
        offset ``counts[shard]``, fuses the local size/counter updates (C3
        done tile-locally; on a 2D mesh the per-row sizes are the one
        vertex-axis psum — a ``(b,)`` int vector, never arena columns),
        and advances the theta shard's count by ``incs[shard]``.
        ``R``/``sizes``/``counter``/``counts`` are donated — the store's
        previous buffers are dead after the call.
      * ``valid(counts, sizes)`` — per-shard prefix mask
        ``local_iota < counts[shard]`` as a global ``P(theta_axes)`` bool
        array (``sizes`` is only a shape donor).

    ``codec`` (a hashable ``repro.core.pack.codec`` tile codec, or None
    for the raw bitmap layout) encodes each tile's batch block before the
    arena write — sizes and counter partials are still computed from the
    *bit* rows, so the fused C3 path is layout-invariant.  Pack-on-write
    is fused: the encoded block is a jit temporary of the write kernel.
    """
    sp_rows, sp_vec = P(theta_axes, vertex_axis), P(theta_axes)
    write = _tile_write_body(codec, vertex_axis)

    write_fn = jax.jit(
        shard_map(write, mesh=mesh,
                  in_specs=(sp_rows, sp_vec, sp_rows, sp_vec, sp_rows,
                            sp_vec),
                  out_specs=(sp_rows, sp_vec, sp_rows, sp_vec)),
        donate_argnums=(0, 1, 2, 3))

    def valid(counts, sizes):
        return jnp.arange(sizes.shape[0], dtype=jnp.int32) < counts[0]

    valid_fn = jax.jit(shard_map(
        valid, mesh=mesh, in_specs=(sp_vec, sp_vec), out_specs=sp_vec))
    return write_fn, valid_fn


@functools.lru_cache(maxsize=None)
def _sharded_hits_kernel(mesh, theta_axes, vertex_axis, codec=None):
    """Membership queries with both arena axes resident: each tile tests
    the queried vertices that fall inside its own column block against its
    own rows; the vertex axis combines per-(row, query) hit bits with one
    psum-or (a ``(cap_local, Q)`` bool — rows x queries, never columns),
    and the theta axis reduces only the final ``(Q,)`` counts.

    ``starts`` is the replicated ``(Dv + 1,) int32`` block-boundary array
    of the store's `VertexPartition` — shard ``s`` owns global vertices
    ``[starts[s], starts[s+1])`` — so one compiled kernel serves equal
    *and* edge-balanced layouts (the boundaries are data, not shape)."""
    sp_rows, sp_vec = P(theta_axes, vertex_axis), P(theta_axes)

    def hits(R, valid, S, starts):
        n_local = R.shape[1] if codec is None else codec.n_cols
        flat = S.reshape(-1)
        if vertex_axis is None:
            lidx, ok = flat, jnp.ones(flat.shape, jnp.bool_)
        else:
            shard = jax.lax.axis_index(vertex_axis)
            lo = starts[shard]
            lidx = flat - lo
            ok = (flat >= lo) & (flat < starts[shard + 1])
        lidx = jnp.clip(lidx, 0, n_local - 1)
        memb = (jnp.take(R, lidx, axis=1) > 0 if codec is None
                else codec.decode_cols(R, lidx))
        memb = (memb & ok[None, :]).reshape((R.shape[0],) + S.shape)
        hit = memb.any(axis=2)                       # (cap_local, Q)
        hit = _psum_if(hit.astype(jnp.int32), vertex_axis) > 0
        hit = hit & valid[:, None]
        counts = jax.lax.psum(
            hit.sum(axis=0).astype(jnp.float32), theta_axes)
        n_valid = jnp.maximum(
            jax.lax.psum(valid.sum(dtype=jnp.float32), theta_axes), 1.0)
        return counts / n_valid

    return jax.jit(shard_map(
        hits, mesh=mesh, in_specs=(sp_rows, sp_vec, P(), P()),
        out_specs=P()))


@functools.lru_cache(maxsize=None)
def _sharded_touch_kernel(mesh, theta_axes, vertex_axis, codec=None):
    """Reverse-touch (streaming invalidation) with both axes local: each
    tile checks the touched vertices inside its own column block against
    its own rows; only the ``(cap_local,)`` per-row partial hit bits cross
    the vertex axis (psum-or), and the result stays ``P(theta_axes)``.
    ``starts`` carries the partition block boundaries, as in
    `_sharded_hits_kernel`."""
    sp_rows, sp_vec = P(theta_axes, vertex_axis), P(theta_axes)

    def touch(R, verts, vmask, starts):
        n_local = R.shape[1] if codec is None else codec.n_cols
        if vertex_axis is None:
            lidx, ok = verts, vmask
        else:
            shard = jax.lax.axis_index(vertex_axis)
            lo = starts[shard]
            lidx = verts - lo
            ok = vmask & (verts >= lo) & (verts < starts[shard + 1])
        lidx = jnp.clip(lidx, 0, n_local - 1)
        memb = (jnp.take(R, lidx, axis=1) > 0 if codec is None
                else codec.decode_cols(R, lidx))
        local = (memb & ok[None, :]).any(axis=1)
        return _psum_if(local.astype(jnp.int32), vertex_axis) > 0

    return jax.jit(shard_map(
        touch, mesh=mesh, in_specs=(sp_rows, P(), P(), P()),
        out_specs=sp_vec))


@functools.lru_cache(maxsize=None)
def _sharded_index_kernels(mesh, theta_axes, vertex_axis, l_pad,
                           codec=None):
    """Per-tile C4 conversion: each (theta, vertex) tile rewrites its own
    ``(cap_local, n_local)`` bitmap block as ``(cap_local, l_pad)``
    *local-id* index lists (sentinel ``n_local``) — no cross-device
    traffic at all; the index view is born with the arena's own 2D
    layout.  ``l_pad`` is the per-vertex-shard C4 width (sized from the
    max *local* set size, which shrinks as vertex shards are added)."""
    sp_rows = P(theta_axes, vertex_axis)

    def convert(R):
        return bitmap_to_indices(R if codec is None else codec.decode(R),
                                 l_pad)

    return jax.jit(shard_map(
        convert, mesh=mesh, in_specs=(sp_rows,), out_specs=sp_rows))


@functools.lru_cache(maxsize=None)
def _sharded_localmax_kernel(mesh, theta_axes, vertex_axis, codec=None):
    """Max per-vertex-shard set size over valid rows — the statistic the
    per-shard C4 threshold keys on.  Tile-local row popcounts, one scalar
    psum-max; nothing row- or column-sized crosses devices."""
    sp_rows, sp_vec = P(theta_axes, vertex_axis), P(theta_axes)

    def localmax(R, valid):
        sz = (R.sum(axis=1, dtype=jnp.int32) if codec is None
              else codec.row_popcount(R))
        sz = sz * valid.astype(jnp.int32)
        m = jnp.max(sz, initial=0)
        axes = theta_axes + ((vertex_axis,) if vertex_axis else ())
        return jax.lax.pmax(m, axes)[None]

    return jax.jit(shard_map(
        localmax, mesh=mesh, in_specs=(sp_rows, sp_vec), out_specs=P()))


@functools.lru_cache(maxsize=None)
def _sharded_grow_kernel(mesh, theta_axes, vertex_axis, pad):
    """Per-shard capacity doubling: every tile zero-pads its own
    ``(cap_local, n_local)`` block to ``(cap_local + pad, n_local)``
    locally (no gather, no cross-device traffic; the copy itself is not
    donatable because the output shape differs, but doubling amortizes
    it).  Live bits pad with True (unfilled slots are live-by-default)."""
    sp_rows, sp_vec = P(theta_axes, vertex_axis), P(theta_axes)

    def grow(R, sizes, live):
        return (jnp.pad(R, ((0, pad), (0, 0))),
                jnp.pad(sizes, ((0, pad),)),
                jnp.pad(live, ((0, pad),), constant_values=True))

    return jax.jit(shard_map(grow, mesh=mesh,
                             in_specs=(sp_rows, sp_vec, sp_vec),
                             out_specs=(sp_rows, sp_vec, sp_vec)))


@functools.lru_cache(maxsize=None)
def _sharded_stream_kernels(mesh, theta_axes, vertex_axis, codec=None):
    """Compiled per-(mesh, axes) streaming row-lifecycle kernels.

    Returns ``(kill, replace, compact)``, each tile-local in *both* axes
    (the kill contribution, the replace scatter, and the compaction
    permutation all act on a tile's own ``(cap_local, n_local)`` block;
    on 2D meshes the only vertex-axis collective is the ``(K,)`` psum of
    replacement row sizes — a reduced quantity, never arena columns):
      * ``kill(R, counter, sizes, live, dead)`` — subtract the dead local
        rows' contribution from the tile's counter partial, zero their
        sizes, clear their live bits.  counter/sizes/live donated.
      * ``replace(R, counter, sizes, live, offs, idx, rows)`` — ``idx``
        arrives replicated and ``rows`` vertex-sharded ``P(None,
        vertex_axis)``; each tile scatters its own column slice of the
        rows whose global slot falls in its theta block (out-of-block
        targets are dropped), revives their live bits, and adds its share
        of the contribution to its counter partial.  All state donated.
      * ``compact(R, sizes, live, counts)`` — stable-partition the live
        local rows to the tile's arena head and return the new per-shard
        counts; dead slots zero out.  The permutation depends only on
        ``P(theta_axes)`` state, so every vertex tile of a theta shard
        permutes its columns identically.  R/sizes donated.
    """
    sp_rows, sp_vec = P(theta_axes, vertex_axis), P(theta_axes)

    def kill(R, counter, sizes, live, dead):
        contrib = kops.arena_count(R, dead, codec=codec)
        counter = counter - contrib.astype(jnp.int32)[None, :]
        return counter, jnp.where(dead, 0, sizes), live & ~dead

    kill_fn = jax.jit(
        shard_map(kill, mesh=mesh,
                  in_specs=(sp_rows, sp_rows, sp_vec, sp_vec, sp_vec),
                  out_specs=(sp_rows, sp_vec, sp_vec)),
        donate_argnums=(1, 2, 3))

    def replace(R, counter, sizes, live, offs, idx, rows):
        cap_local = R.shape[0]
        lidx = idx - offs[0]
        ok = (lidx >= 0) & (lidx < cap_local)
        tgt = jnp.where(ok, lidx, cap_local)        # OOB -> dropped
        stored = rows if codec is None else codec.encode(rows)
        R = R.at[tgt].set(stored, mode="drop")
        contrib = (rows * ok[:, None]).sum(axis=0, dtype=jnp.int32)
        counter = counter + contrib[None, :]
        row_sizes = _psum_if(rows.sum(axis=1, dtype=jnp.int32), vertex_axis)
        sizes = sizes.at[tgt].set(row_sizes, mode="drop")
        live = live.at[tgt].set(True, mode="drop")
        return R, counter, sizes, live

    replace_fn = jax.jit(
        shard_map(replace, mesh=mesh,
                  in_specs=(sp_rows, sp_rows, sp_vec, sp_vec, sp_vec,
                            P(None), P(None, vertex_axis)),
                  out_specs=(sp_rows, sp_rows, sp_vec, sp_vec)),
        donate_argnums=(0, 1, 2, 3))

    def comp(R, sizes, live, counts):
        cap_local = R.shape[0]
        iota = jnp.arange(cap_local, dtype=jnp.int32)
        keep = (iota < counts[0]) & live
        perm = jnp.argsort(jnp.where(keep, 0, 1) * cap_local + iota)
        newvalid = iota < keep.sum(dtype=jnp.int32)
        R = jnp.where(newvalid[:, None], R[perm], 0)
        sizes = jnp.where(newvalid, sizes[perm], 0)
        return R, sizes, keep.sum(dtype=jnp.int32)[None]

    comp_fn = jax.jit(
        shard_map(comp, mesh=mesh,
                  in_specs=(sp_rows, sp_vec, sp_vec, sp_vec),
                  out_specs=(sp_rows, sp_vec, sp_vec)),
        donate_argnums=(0, 1))

    return kill_fn, replace_fn, comp_fn


@functools.lru_cache(maxsize=None)
def _sharded_recode_kernel(mesh, theta_axes, vertex_axis, codec_from,
                           codec_to):
    """Tile-local arena re-encode (``codec_from`` -> ``codec_to``) — the
    compress-ladder morph and token-width growth both route here.  Each
    tile decodes and re-encodes its own block; the decoded bits are a jit
    temporary, nothing crosses devices, and the output is born in the
    arena's own ``P(theta_axes, vertex_axis)`` layout (not donatable —
    the at-rest width changes)."""
    sp_rows = P(theta_axes, vertex_axis)

    def recode(R):
        return codec_to.encode(codec_from.decode(R))

    return jax.jit(shard_map(
        recode, mesh=mesh, in_specs=(sp_rows,), out_specs=sp_rows))


@functools.lru_cache(maxsize=None)
def _sharded_tokneed_kernel(mesh, theta_axes, vertex_axis, codec=None):
    """Max per-tile token count of an arena (or batch) — the statistic
    that sizes a `TokenCodec`'s ``s_pad`` before a compress-ladder morph
    or a token-width growth.  Tile-local `tokens_needed` row maxima, one
    scalar pmax over every mesh axis.  ``codec`` decodes an encoded
    resident arena first; None reads raw bit rows (a staged batch)."""
    from repro.core.pack.codec import tokens_needed
    sp_rows = P(theta_axes, vertex_axis)

    def need(X):
        bits = X if codec is None else codec.decode(X)
        m = jnp.max(tokens_needed(bits), initial=0)
        axes = theta_axes + ((vertex_axis,) if vertex_axis else ())
        return jax.lax.pmax(m, axes)[None]

    return jax.jit(shard_map(
        need, mesh=mesh, in_specs=(sp_rows,), out_specs=P()))


def _tile_codec(kind: str, n_cols: int, s_pad=None):
    """Per-tile codec for encoded sharded arenas (lazy import — the pack
    package itself imports this module)."""
    from repro.core.pack.codec import MIN_TOKEN_PAD, codec_for
    return codec_for(kind, n_cols,
                     MIN_TOKEN_PAD if s_pad is None else int(s_pad))


def _pad_cols(rows, n_pad: int):
    """Zero-pad ``(B, n)`` uint8 rows to the vertex-padded column count
    (a no-op on 1D/single-vertex layouts where ``n_pad == n``)."""
    pad = n_pad - rows.shape[1]
    if pad == 0:
        return rows
    return jnp.concatenate(
        [rows, jnp.zeros((rows.shape[0], pad), rows.dtype)], axis=1)


class ShardedStore:
    """Mesh-sharded dense bitmap arena — the paper's C1 RRR-set
    partitioning applied to the *store itself*, not just selection, on a
    mesh that can be 1D (theta only) or genuinely 2D (theta x vertex).

    State layout over ``Dt = prod(mesh.shape[a] for a in theta_axes)``
    theta shards and ``Dv = mesh.shape[vertex_axis]`` vertex shards
    (``Dv = 1`` when ``vertex_axis`` is None — the historical 1D layout):

      * ``R``       — ``(Dt * cap_local, n_pad) uint8``,
        ``P(theta_axes, vertex_axis)``: tile ``(t, v)`` owns rows
        ``[t * cap_local, (t+1) * cap_local)`` x columns
        ``[v * n_local, (v+1) * n_local)``, where ``n_local`` is the
        padded tile width of the store's `VertexPartition` and ``n_pad =
        Dv * n_local`` (pad columns carry no vertex and stay all-zero).
        The full ``(theta, n)`` arena never exists on one device;
        per-device memory is ``cap_local * n_local`` bytes, so **theta
        scales with the theta axis and n with the vertex axis** — graph
        size scales with the mesh, not with one device.  The layout may
        be the canonical equal blocks (``vertex_partition``; tile ``v``
        holds vertices ``[v * n_local, (v+1) * n_local)``) or an
        edge-balanced one (``balanced_vertex_partition``; tile ``v``
        holds the contiguous run ``[starts[v], starts[v+1])`` with
        data-dependent boundaries, padded to ``n_local`` columns) — both
        shared with selection and streaming reverse-touch through
        ``self.partition``.
      * ``sizes``   — ``(Dt * cap_local,) int32``, ``P(theta_axes)``
        (replicated over the vertex axis), aligned with ``R`` rows.
      * counter     — per-tile partials ``(Dt, n_pad) int32``,
        ``P(theta_axes, vertex_axis)`` — tile ``(t, v)`` counts its own
        rows over its own columns (the ``(Dt, Dv, n/Dv)`` partial layout,
        stored as a 2D array); the ``counter`` property reduces them to
        the global fused counter for host consumers (selection never
        needs it — it reduces tile-locally and psums).
      * row counts  — ``(Dt,) int32``, ``P(theta_axes)``, plus a host
        mirror that drives growth logic without device syncs.

    ``add_batch`` splits each sampled batch into Dt equal row blocks
    (zero-padding rows to ``ceil(B / Dt) * Dt`` and columns to ``n_pad``;
    pad rows are masked, not counted) and runs the donated shard_map
    write kernel: each tile writes its (row block, column block) of the
    batch into its local arena slot and fuses its local size / counter
    updates.  Capacity grows *per shard* by amortized doubling
    (``cap_local`` is a power of two), so jit retraces stay O(log theta)
    and growth copies are device-local.

    Placement across tiles is a layout detail: selection, ``hits``
    and the global counter are permutation-invariant over rows and exact
    integer sums over columns, so a `ShardedStore` fed the same sample
    stream as a `BitmapStore` yields bit-identical selections on any
    mesh shape — 1 device, 1D, or 2D.

    ``snapshot``/``restore`` go through ``state()``/``from_state``: the
    snapshot stores valid rows *compacted* on host (shard order, vertex
    padding stripped), so a snapshot taken on one layout restores onto
    any other — none <-> 1D <-> 2D — or into a plain `BitmapStore` when
    no mesh is available (see `store_from_state`).
    """

    def __init__(self, n: int, *, mesh, theta_axes=("data",),
                 vertex_axis=None, capacity: int = MIN_CAPACITY,
                 policy: StorePressurePolicy | None = None,
                 partition: VertexPartition | None = None,
                 codec: str = "bitmap", s_pad=None):
        if mesh is None:
            raise ValueError("ShardedStore needs a jax.sharding.Mesh")
        if isinstance(theta_axes, str):
            theta_axes = (theta_axes,)
        self.n = int(n)
        self.mesh = mesh
        self.theta_axes = tuple(theta_axes)
        self.vertex_axis = vertex_axis
        self.D = int(np.prod([mesh.shape[a] for a in self.theta_axes]))
        self.Dv = int(mesh.shape[vertex_axis]) if vertex_axis else 1
        if partition is None:
            partition = vertex_partition(self.n, self.Dv)
        elif partition.n != self.n or partition.shards != self.Dv:
            raise ValueError(
                f"partition covers n={partition.n} over "
                f"{partition.shards} shards; this store needs n={self.n} "
                f"over Dv={self.Dv}")
        self.partition = partition
        self.n_local, self.n_pad = partition.block, partition.n_pad
        # the per-tile at-rest codec: "bitmap" keeps the historical raw
        # layout; "packed"/"compressed" store each (theta, vertex) tile
        # encoded — every kernel decodes tile-locally (IMPack)
        self.codec = _tile_codec(codec, self.n_local, s_pad)
        self.w_local = self.codec.width
        self.w_pad = self.Dv * self.w_local
        self.cap_local = next_pow2(-(-int(capacity) // self.D))
        self.version = 0
        self.policy = policy
        self.track_remaps = False
        self._remaps: list[np.ndarray] = []
        self._sh_rows = NamedSharding(
            mesh, P(self.theta_axes, vertex_axis))
        self._sh_vec = NamedSharding(mesh, P(self.theta_axes))
        self._sh_rep = NamedSharding(mesh, P())
        self._sh_vrows = NamedSharding(mesh, P(None, vertex_axis))
        # partition block boundaries, replicated for the starts-aware
        # kernels; balanced layouts also carry the column gather maps
        # (global order <-> padded layout) — host-precomputed, O(n)
        self._starts_dev = jax.device_put(
            jnp.asarray(partition.starts, jnp.int32), self._sh_rep)
        if partition.is_equal:
            self._col_src = self._col_ok = self._cols_from_pad = None
        else:
            src = partition.source_cols()
            self._col_src = jnp.asarray(
                np.clip(src, 0, max(self.n - 1, 0)), jnp.int32)
            self._col_ok = jnp.asarray((src < self.n).astype(np.uint8))
            self._cols_from_pad = partition.padded_cols()
        if policy is not None:
            cap = policy.row_cap(self._row_bytes())
            if cap // self.D < 1:
                raise ValueError(
                    f"policy row cap {cap} is below one row per shard "
                    f"(D={self.D})")
            self.cap_local = min(self.cap_local, cap // self.D)
        # what `reset` returns to
        self._empty_as = (self.cap_local, self.codec)
        self._bind_kernels()
        self._alloc_empty()

    def _alloc_empty(self):
        """An empty arena at the current capacity and codec, born
        sharded, with its host mirrors."""
        self._counts_host = np.zeros((self.D,), np.int64)
        self._live_host = np.ones((self.D * self.cap_local,), bool)
        # (first set, rows per shard, shard counts before) of each write
        # since the arena was emptied, in write order: where `_set_slots`
        # finds a set; None once rows have moved or been overwritten
        self._writes = []
        self._written = 0
        self.R = _sharded_zeros(
            (self.D * self.cap_local, self.w_pad), self.codec.dtype,
            self._sh_rows)
        self.sizes = _sharded_zeros(
            (self.D * self.cap_local,), jnp.int32, self._sh_vec)
        self.live = _sharded_ones(
            (self.D * self.cap_local,), jnp.bool_, self._sh_vec)
        self._counter = _sharded_zeros(
            (self.D, self.n_pad), jnp.int32, self._sh_rows)
        self._counts = _sharded_zeros((self.D,), jnp.int32, self._sh_vec)
        self._idx_cache = None      # (version, l_pad) -> sharded R_idx

    def reset(self) -> None:
        """Empty the arena in place: capacity and codec back to those it
        was built with, every row, size, count and counter partial zero,
        as a fresh store on the same mesh and partition.  The object
        stays the one the engine and its fused extender hold, so the
        next IMM run on the engine samples into it with the programs it
        has compiled.  The version moves on, never back, so no answer
        memoized over the old rows is served over the new ones."""
        cap_local, codec = self._empty_as
        self.R = None               # free the old tiles first
        self.cap_local = cap_local
        if codec != self.codec:
            self.codec = codec
            self.w_local = codec.width
            self.w_pad = self.Dv * self.w_local
            self._bind_kernels()
        self._remaps = []
        self._alloc_empty()
        self.version += 1

    def _bind_kernels(self):
        """(Re)bind the per-(mesh, axes, codec) compiled kernels — called
        at construction and after every codec morph.  ``_codec_arg`` is
        None for the raw bitmap layout so the historical kernel cache
        keys keep serving bitmap stores unchanged."""
        codec = None if self.codec.kind == "bitmap" else self.codec
        self._codec_arg = codec
        self._write_fn, self._valid_fn = _sharded_write_kernels(
            self.mesh, self.theta_axes, self.vertex_axis, codec)
        self._kill_fn, self._replace_fn, self._compact_fn = (
            _sharded_stream_kernels(
                self.mesh, self.theta_axes, self.vertex_axis, codec))
        self._hits_fn = _sharded_hits_kernel(
            self.mesh, self.theta_axes, self.vertex_axis, codec)

    def _row_bytes(self) -> int:
        """Physical at-rest bytes per global row — what byte-budget
        pressure policies meter.  Bitmap rows keep the historical
        logical-``n`` accounting (1 byte/vertex); encoded rows charge the
        padded tile width times the codec element size."""
        if self.codec.kind == "bitmap":
            return self.n
        return self.w_pad * jnp.dtype(self.codec.dtype).itemsize

    def _set_codec(self, codec):
        """Morph the resident arena to ``codec`` in place (tile-local
        decode/re-encode), rebind kernels, and invalidate derived
        views."""
        if codec == self.codec:
            return
        rec = _sharded_recode_kernel(
            self.mesh, self.theta_axes, self.vertex_axis, self.codec, codec)
        self.R = rec(self.R)
        self.codec = codec
        self.w_local = codec.width
        self.w_pad = self.Dv * self.w_local
        self._bind_kernels()
        self._idx_cache = None
        self.version += 1

    def _widen_tokens(self, rows_bits=None):
        """Grow the token codec's ``s_pad`` to fit ``rows_bits`` (a
        staged sharded bit batch; None re-measures the resident arena) —
        the `IndexStore` ``_widen`` analogue for compressed tiles."""
        from repro.core.pack.codec import MIN_TOKEN_PAD, TokenCodec
        if rows_bits is None:
            fn = _sharded_tokneed_kernel(
                self.mesh, self.theta_axes, self.vertex_axis,
                self._codec_arg)
            need = int(np.asarray(fn(self.R))[0])
        else:
            fn = _sharded_tokneed_kernel(
                self.mesh, self.theta_axes, self.vertex_axis, None)
            need = int(np.asarray(fn(rows_bits))[0])
        s_new = next_pow2(max(need, MIN_TOKEN_PAD), self.codec.s_pad)
        if s_new > self.codec.s_pad:
            self._set_codec(TokenCodec(self.n_local, s_new))

    def _compress_step(self) -> bool:
        """One rung up the policy's compress-before-evict ladder (see
        `StorePressurePolicy.ladder`): morph the arena to the next
        denser at-rest codec and report whether anything changed."""
        ladder = self.policy.ladder if self.policy is not None else ()
        nxt = _ladder_next(self.codec.kind, ladder)
        if nxt is None:
            return False
        if nxt == "compressed":
            from repro.core.pack.codec import MIN_TOKEN_PAD, TokenCodec
            fn = _sharded_tokneed_kernel(
                self.mesh, self.theta_axes, self.vertex_axis,
                self._codec_arg)
            need = int(np.asarray(fn(self.R))[0])
            new = TokenCodec(self.n_local,
                             next_pow2(max(need, 1), MIN_TOKEN_PAD))
        else:
            new = _tile_codec(nxt, self.n_local)
        self._set_codec(new)
        obs.counter("store.compress_steps").add(1)
        return True

    # ------------------------------------------------------------ shape ----

    @property
    def representation(self) -> str:
        """The at-rest tile codec kind (``"bitmap"``/``"packed"``/
        ``"compressed"``) — what engines dispatch selection on."""
        return self.codec.kind

    @property
    def capacity(self) -> int:
        """Global row capacity (``D * cap_local``)."""
        return self.D * self.cap_local

    @property
    def count(self) -> int:
        """Total stored RRR sets across all shards."""
        return int(self._counts_host.sum())

    @property
    def counts(self) -> np.ndarray:
        """Per-shard valid row counts ``(D,)`` (host copy)."""
        return self._counts_host.copy()

    def _filled_host(self) -> np.ndarray:
        """Host ``(D * cap_local,) bool`` per-shard fill-prefix mask."""
        iota = np.arange(self.cap_local)
        return (iota[None, :] < self._counts_host[:, None]).reshape(-1)

    @property
    def dead(self) -> int:
        """Filled rows whose live bit is cleared (stale/evicted)."""
        return int((self._filled_host() & ~self._live_host).sum())

    @property
    def live_count(self) -> int:
        """Filled rows that are still live (the streaming effective
        theta)."""
        return self.count - self.dead

    @property
    def row_cap(self) -> int | None:
        """Attainable policy row capacity, or None when unbounded.
        Floored to a multiple of the shard count (each shard holds
        ``cap // D`` rows) — reporting the raw policy cap would make
        ``extend``-to-cap loops spin forever on non-divisible caps."""
        if self.policy is None:
            return None
        cap = self.policy.row_cap(self._row_bytes())
        return (cap // self.D) * self.D

    def live_mask(self) -> jnp.ndarray:
        """Sharded ``(D * cap_local,) bool`` live bits."""
        return self.live

    def drain_remaps(self) -> list[np.ndarray]:
        """Pop slot remaps recorded since the last drain (compactions
        *and* per-shard growth — growth renumbers global slots because
        shard blocks move apart).  Only populated while ``track_remaps``
        is set."""
        out, self._remaps = self._remaps, []
        return out

    @property
    def counter(self) -> jnp.ndarray:
        """Global fused counter ``(n,) int32`` — reduces the per-tile
        partials over the theta axis and strips the vertex padding
        columns (an all-reduce; host/reporting use only, the selection
        kernels consume the partials tile-locally).  Always in *global*
        vertex order, whatever the column layout."""
        total = self._counter.sum(axis=0)
        if self.partition.is_equal:
            return total[:self.n]
        return jnp.take(total, jnp.asarray(self._cols_from_pad), axis=0)

    @property
    def batch_sharding(self) -> NamedSharding:
        """Sharding a sampler should place its ``(B, n)`` batch with so
        the store write is a pure device-local slice update (rows
        block-partitioned over ``theta_axes``, vertex columns over
        ``vertex_axis`` when the mesh is 2D) — each device samples
        exactly the (row, column) tile its arena shard will store.
        Under a *balanced* partition, GSPMD's equal column tiling of the
        ``(B, n)`` batch does not coincide with the arena's
        data-dependent boundaries; ``add_batch``'s layout gather performs
        the boundary re-tiling on the (small) batch, so traversal keeps
        its shape-stable equal tiling (and with it the positional coin
        streams) while the resident arena stays edge-balanced."""
        return self._sh_rows

    # ---------------------------------------------------------- writing ----

    def _layout_cols(self, rows):
        """Rearrange ``(B, n)`` global-order rows into the arena's padded
        column layout ``(B, n_pad)``: a zero-pad for the equal-block
        layout (columns already line up), a masked column gather for
        balanced layouts (pad columns land all-zero)."""
        if self.partition.is_equal:
            return _pad_cols(rows, self.n_pad)
        return (jnp.take(rows, self._col_src, axis=1)
                * self._col_ok[None, :].astype(rows.dtype))

    def _grow_rows(self, incoming: int):
        need = int(self._counts_host.max(initial=0)) + incoming
        new_cap = next_pow2(need, self.cap_local)
        cap = self.row_cap
        if cap is not None:
            new_cap = min(new_cap, max(cap // self.D, self.cap_local))
        if new_cap == self.cap_local:
            return
        grow = _sharded_grow_kernel(
            self.mesh, self.theta_axes, self.vertex_axis,
            new_cap - self.cap_local)
        # bytes from shapes: each tile pads its arena, sizes and live
        # rows, reading the old rows and writing the new capacity
        old, new = self.D * self.cap_local, self.D * new_cap
        with obs.span("store.grow", tier="store", old_capacity=old,
                      new_capacity=new,
                      bytes=(new + old) * (self._row_bytes() + 4 + 1)):
            self.R, self.sizes, self.live = grow(self.R, self.sizes,
                                                 self.live)
        # shard blocks moved apart: global slot d*cap_local+i is now
        # d*new_cap+i — record the renumbering for provenance trackers
        old_cap = self.cap_local
        live_host = np.ones((self.D * new_cap,), bool)
        remap = np.empty((self.D * old_cap,), np.int64)
        for d in range(self.D):
            remap[d * old_cap:(d + 1) * old_cap] = (
                d * new_cap + np.arange(old_cap))
            live_host[d * new_cap:d * new_cap + old_cap] = (
                self._live_host[d * old_cap:(d + 1) * old_cap])
        self._live_host = live_host
        if self.track_remaps:
            self._remaps.append(remap)
        self.cap_local = new_cap

    def reserve(self, sets: int) -> None:
        """Grow every shard's tile now, along its pow2 ladder, to hold
        ``sets`` more sets: the growth the writes would make, made ahead
        by the same per-shard program, rows and write order unchanged.
        A caller that knows its target (IMM's theta for a round, or a
        warm-up of each rung) grows without a write."""
        self._grow_rows(-(-int(sets) // self.D))

    def _ensure_room(self, b: int):
        """Per-shard pressure enforcement: compact away dead rows first,
        then climb the policy's compress ladder (each morph shrinks
        ``_row_bytes`` and so *raises* the byte-budget row cap), and only
        then evict each over-full shard's oldest live rows FIFO."""
        cap = self.row_cap
        if cap is None:
            return
        local_cap = cap // self.D
        if (int(self._counts_host.max(initial=0)) + b > local_cap
                and self.dead):
            self.compact()
        while (int(self._counts_host.max(initial=0)) + b > local_cap
               and self._compress_step()):
            cap = self.row_cap
            local_cap = cap // self.D
        if b > local_cap:
            raise ValueError(
                f"batch of {b} rows per shard exceeds the per-shard "
                f"policy cap of {local_cap} (row cap {cap} over "
                f"{self.D} shards)")
        if int(self._counts_host.max(initial=0)) + b <= local_cap:
            return
        self.compact()
        over = self._counts_host + b - local_cap
        if (over > 0).any():
            mask = np.zeros((self.D * self.cap_local,), bool)
            for d in range(self.D):
                if over[d] > 0:
                    lo = d * self.cap_local
                    mask[lo:lo + int(over[d])] = True
            evicted = self.kill_rows(mask)
            obs.counter("store.rows_evicted").add(evicted)
            self.compact()

    def add_batch(self, visited, counter=None) -> np.ndarray:
        """Append ``visited (B, n) uint8`` rows, block-split across shards.

        Shard ``d`` receives rows ``[d*b, (d+1)*b)`` of the (zero-padded)
        batch, where ``b = ceil(B / D)``, and writes them at its local
        offset in place — the arena, sizes, counter and counts buffers are
        all donated, so outstanding views are invalidated.  ``counter`` is
        accepted for `RRRStore` API parity but ignored: the fused C3
        contribution is recomputed *inside* the write kernel from each
        shard's own rows, keeping the count device-local.  Returns the
        global slot index of each batch row (provenance for streaming
        consumers); under a `StorePressurePolicy` the write may first
        compact and evict per shard.
        """
        del counter  # recomputed shard-locally inside the write kernel
        with obs.span("store.write", tier="store", kind="sharded"):
            visited = jnp.asarray(visited).astype(jnp.uint8)
            B = int(visited.shape[0])
            if B == 0:
                return np.zeros((0,), np.int64)
            visited = self._layout_cols(visited)
            b = -(-B // self.D)
            if b * self.D != B:
                visited = jnp.concatenate(
                    [visited,
                     jnp.zeros((b * self.D - B, self.n_pad), jnp.uint8)])
            # no-op when the sampler already placed the batch with
            # ``batch_sharding``; otherwise reshards the (small) batch only
            visited = jax.device_put(visited, self._sh_rows)
            if self.codec.kind == "compressed":
                self._widen_tokens(visited)
            kind_before = self.codec.kind
            self._ensure_room(b)
            if (self.codec.kind == "compressed"
                    and kind_before != "compressed"):
                # the pressure ladder just morphed to tokens sized off the
                # resident rows — the incoming batch may need wider ones
                self._widen_tokens(visited)
            self._grow_rows(b)
            incs_np = np.clip(B - np.arange(self.D) * b, 0, b).astype(np.int32)
            incs = jax.device_put(jnp.asarray(incs_np), self._sh_vec)
            slots = np.empty((B,), np.int64)
            for d in range(self.D):
                i0 = d * b
                cnt = int(incs_np[d])
                slots[i0:i0 + cnt] = (d * self.cap_local
                                      + self._counts_host[d] + np.arange(cnt))
            self.R, self.sizes, self._counter, self._counts = self._write_fn(
                self.R, self.sizes, self._counter, self._counts, visited, incs)
            self._counts_host += incs_np
        self._note_write(B)
        return slots

    def _note_write(self, B: int):
        """Host-side bookkeeping after ``B`` rows landed (``count`` is
        derived from ``_counts_host``, so unlike the arena stores only
        the version bump and gauges live here).  Shared by `add_batch`
        and the fused write chain (`repro.core.fused`), which both place
        row ``r`` of the write in theta shard ``r // ceil(B / D)`` and
        have advanced the shard counts already."""
        self.version += 1
        if self._writes is not None:
            b = -(-B // self.D)
            incs = np.clip(B - np.arange(self.D) * b, 0, b)
            self._writes.append((self._written, b, self._counts_host - incs))
            self._written += B
        if obs.enabled():
            # host arithmetic on shard shapes only — never a device read;
            # byte gauges report *physical* at-rest bytes (the encoded
            # tile width), not the logical uint8 bitmap footprint
            itemsize = jnp.dtype(self.codec.dtype).itemsize
            arena = self.D * self.cap_local * self.w_pad * itemsize
            obs.counter("store.rows_written").add(B)
            obs.gauge("store.occupancy").set(self.count / self.capacity)
            obs.gauge("store.arena_bytes").set(arena)
            obs.gauge("store.bytes_per_device").set(
                self.cap_local * self.w_local * itemsize)
            obs.gauge("store.compress_ratio").set(
                self.D * self.cap_local * self.n_pad / max(arena, 1))

    # ----------------------------------------------------- row lifecycle ----

    def kill_rows(self, dead) -> int:
        """Mark rows dead shard-locally: each shard subtracts its dead
        rows' contribution from its own counter partial (nothing crosses
        devices).  ``dead`` is a global ``(D * cap_local,) bool`` mask
        (host or device); bits outside filled-and-live rows are ignored.
        Returns the number of newly dead rows."""
        dead_host = np.asarray(dead).astype(bool)
        dead_host &= self._filled_host() & self._live_host
        k = int(dead_host.sum())
        if k == 0:
            return 0
        dead_dev = jax.device_put(jnp.asarray(dead_host), self._sh_vec)
        self._counter, self.sizes, self.live = self._kill_fn(
            self.R, self._counter, self.sizes, self.live, dead_dev)
        self._live_host &= ~dead_host
        self.version += 1
        obs.counter("store.rows_killed").add(k)
        return k

    def replace_rows(self, idx, rows) -> None:
        """Overwrite dead slots with fresh rows (streaming refresh).
        ``idx`` is replicated into the kernel and ``rows`` enters
        vertex-sharded (``P(None, vertex_axis)``); each tile scatters
        only its own column slice of the targets inside its theta block.
        Targets must be filled, dead slots (enforced on host); ``idx``
        entries of -1 are padding (the batch pads to a power of two to
        bound retraces)."""
        idx = np.asarray(idx, np.int64)
        real = idx >= 0
        k = int(real.sum())
        if k == 0:
            return
        filled = self._filled_host()
        if ((idx[real] >= self.D * self.cap_local).any()
                or not filled[idx[real]].all()
                or self._live_host[idx[real]].any()):
            raise ValueError(
                "replace_rows targets must be filled, dead slots "
                "(kill_rows them first)")
        with obs.span("store.write", tier="store", kind="sharded-replace"):
            rows = self._layout_cols(jnp.asarray(rows).astype(jnp.uint8))
            if self.codec.kind == "compressed":
                from repro.core.pack.codec import (
                    MIN_TOKEN_PAD, TokenCodec, tokens_needed)
                need = int(jnp.max(
                    tokens_needed(rows.reshape(-1, self.n_local)),
                    initial=0))
                s_new = next_pow2(max(need, MIN_TOKEN_PAD),
                                  self.codec.s_pad)
                if s_new > self.codec.s_pad:
                    self._set_codec(TokenCodec(self.n_local, s_new))
            pad = next_pow2(idx.shape[0], 1) - idx.shape[0]
            if pad:
                idx = np.concatenate([idx, np.full(pad, -1, np.int64)])
                rows = jnp.concatenate(
                    [rows, jnp.zeros((pad, rows.shape[1]), jnp.uint8)])
                real = idx >= 0
            rows = jax.device_put(rows, self._sh_vrows)
            idx_dev = jax.device_put(jnp.asarray(idx, jnp.int32),
                                     self._sh_rep)
            offs = jax.device_put(
                jnp.arange(self.D, dtype=jnp.int32) * self.cap_local,
                self._sh_vec)
            self.R, self._counter, self.sizes, self.live = self._replace_fn(
                self.R, self._counter, self.sizes, self.live, offs, idx_dev,
                rows)
            self._live_host[idx[real]] = True
            self._writes = None
            self.version += 1
        obs.counter("store.rows_replaced").add(k)

    def compact(self) -> np.ndarray | None:
        """Rewrite each shard's live rows to its arena-block head in
        place, reclaiming dead slots shard-locally.  Returns the global
        old->new slot remap (-1 for reclaimed), or None if no shard had
        dead rows."""
        if self.dead == 0:
            return None
        keep = self._filled_host() & self._live_host
        self.R, self.sizes, self._counts = self._compact_fn(
            self.R, self.sizes, self.live, self._counts)
        self.live = _sharded_ones(
            (self.D * self.cap_local,), jnp.bool_, self._sh_vec)
        remap = np.full((self.D * self.cap_local,), -1, np.int64)
        for d in range(self.D):
            lo = d * self.cap_local
            kd = keep[lo:lo + self.cap_local]
            nkeep = int(kd.sum())
            remap[lo:lo + self.cap_local][kd] = lo + np.arange(nkeep)
            self._counts_host[d] = nkeep
        self._live_host = np.ones((self.D * self.cap_local,), bool)
        self._writes = None
        self.version += 1
        obs.counter("store.compactions").add(1)
        if self.track_remaps:
            self._remaps.append(remap)
        return remap

    # ---------------------------------------------------------- reading ----

    def _set_slots(self, sets) -> np.ndarray:
        """Global arena slots of the sets numbered ``sets`` in write
        order since the arena was built or emptied (set ``i`` of a write
        of ``B`` sets that began at set ``f`` is row ``i - f`` of that
        batch).  Slots follow growth; after a compaction or a
        replacement moved or overwrote rows, sets have no number."""
        if self._writes is None:
            raise ValueError("rows were compacted or replaced since the "
                             "arena was emptied: sets have no write order")
        sets = np.asarray(sets, np.int64).reshape(-1)
        if sets.size and (sets.min() < 0 or sets.max() >= self._written):
            raise IndexError(f"sets {sets} outside the {self._written} "
                             "written since the arena was emptied")
        first = np.asarray([w[0] for w in self._writes], np.int64)
        out = np.empty(sets.shape, np.int64)
        for j, (i, w) in enumerate(zip(
                sets, np.searchsorted(first, sets, side="right") - 1)):
            f, b, before = self._writes[w]
            d, r = divmod(int(i - f), b)
            out[j] = d * self.cap_local + before[d] + r
        return out

    def _global_cols(self, R: np.ndarray) -> np.ndarray:
        """Host rows of the arena, ``(k, w_pad)`` at rest, as ``(k, n)``
        bit rows in global vertex order: decoded per vertex tile, pad
        columns stripped, the partition's column layout undone."""
        if self.codec.kind != "bitmap":
            R = np.concatenate(
                [self.codec.decode_np(
                    R[:, v * self.w_local:(v + 1) * self.w_local])
                 for v in range(self.Dv)], axis=1)
        return (R[:, :self.n] if self.partition.is_equal
                else R[:, self._cols_from_pad])

    def read_sets(self, sets) -> np.ndarray:
        """The sets numbered ``sets`` (`_set_slots`) as ``(k, n) uint8``
        host rows in global vertex order, equal to the rows a
        `BitmapStore` fed the same batches holds at those indices.  Each
        device gives up only the requested rows of its own tile."""
        slots = self._set_slots(sets)
        out = np.zeros((slots.size, self.w_pad), self.codec.dtype)
        for sh in self.R.addressable_shards:
            rows, cols = sh.index
            lo = rows.start or 0
            hi = self.R.shape[0] if rows.stop is None else rows.stop
            pick = (slots >= lo) & (slots < hi)
            if pick.any():
                out[pick, cols] = np.asarray(
                    sh.data[jnp.asarray(slots[pick] - lo)])
        return self._global_cols(out)

    def valid_mask(self) -> jnp.ndarray:
        """Sharded ``(D * cap_local,) bool`` mask of filled *live* rows
        (the per-shard prefix ``local_iota < counts[shard]``, minus any
        rows killed by streaming invalidation/eviction)."""
        return self._valid_fn(self._counts, self.sizes) & self.live

    def view(self) -> StoreView:
        """`StoreView` over the *sharded* arena: ``R`` keeps its
        ``P(theta_axes, vertex_axis)`` layout and ``valid`` its
        ``P(theta_axes)`` layout, so sharded selection strategies consume
        the tiles natively (zero resharding on entry).  Aliases live
        buffers — consume before the next ``add_batch``."""
        return StoreView(self.representation, self.R, self.valid_mask(),
                         self.n, self.count)

    def hits(self, S) -> jnp.ndarray:
        """Covered fraction per query: ``S (Q, L) int32`` -> ``(Q,) f32``.
        Each tile tests membership of the queried vertices inside its own
        column block against its own rows; only per-(row, query) hit bits
        cross the vertex axis and per-query counts the theta axis (never
        arena rows or columns)."""
        with obs.span("count", tier="store", kind="sharded"):
            return self._hits_fn(self.R, self.valid_mask(),
                                 jnp.asarray(S, jnp.int32), self._starts_dev)

    def coverage_stats(self) -> tuple[float, int]:
        """(avg fractional set coverage, max set size) over live stored
        sets (killed rows have their sizes zeroed)."""
        return _coverage_stats(self.sizes, self.live_count, self.n)

    def max_local_size(self) -> int:
        """Max per-vertex-shard set size over valid rows — the statistic
        the per-shard C4 representation threshold keys on (each vertex
        shard sees only its ``n_local`` columns of every set, so local
        sizes shrink as vertex shards are added).  Cached per store
        version: one select calls this twice (representation choice,
        then index-view width) and must not launch the collective kernel
        and block on the host both times."""
        cache = getattr(self, "_localmax_cache", None)
        if cache is not None and cache[0] == self.version:
            return cache[1]
        fn = _sharded_localmax_kernel(
            self.mesh, self.theta_axes, self.vertex_axis, self._codec_arg)
        val = int(np.asarray(fn(self.R, self.valid_mask()))[0])
        self._localmax_cache = (self.version, val)
        return val

    def index_view(self, l_pad: int) -> StoreView:
        """Sharded C4 index view: each tile rewrites its own bitmap block
        as ``(cap_local, l_pad)`` *local-id* index lists (sentinel
        ``n_local``), entirely device-local — the view keeps the arena's
        ``P(theta_axes, vertex_axis)`` layout, so the sharded-sparse
        selection strategy consumes it with zero resharding.  Cached
        until the arena next changes."""
        key = (self.version, int(l_pad))
        if self._idx_cache is None or self._idx_cache[0] != key:
            fn = _sharded_index_kernels(
                self.mesh, self.theta_axes, self.vertex_axis, int(l_pad),
                self._codec_arg)
            self._idx_cache = (key, fn(self.R))
        return StoreView("indices", self._idx_cache[1], self.valid_mask(),
                         self.n, self.count)

    def rows_touching_cols(self, verts, vmask) -> jnp.ndarray:
        """``(capacity,) bool`` rows whose bitmap has a set bit in any
        masked ``verts`` column — the streaming reverse-touch query,
        tile-local in both axes (`repro.stream.invalidate` dispatches
        here on sharded stores)."""
        fn = _sharded_touch_kernel(
            self.mesh, self.theta_axes, self.vertex_axis, self._codec_arg)
        return fn(self.R, jnp.asarray(verts, jnp.int32),
                  jnp.asarray(vmask, jnp.bool_), self._starts_dev)

    # ------------------------------------------------------ checkpointing ----

    def state(self) -> dict:
        """Host snapshot pytree (kind tag ``"sharded"``): the *live*
        valid rows of every shard compacted into a contiguous
        ``(live_count, n)`` array (shard order, vertex padding columns
        stripped) — stale/killed rows are dropped at snapshot time — so
        restore redistributes onto any mesh layout (none <-> 1D <-> 2D),
        the elastic layout `checkpoint.store` promises.  This is the one
        deliberate host gather in the store's life cycle.  Rows are put
        back in *global* vertex-id order whatever the column layout, so
        a snapshot taken under a balanced partition restores onto equal
        blocks (or different balanced boundaries) unchanged — restore
        re-partitions elastically.  Encoded (packed/compressed) arenas
        are decoded per vertex tile on host first — snapshot rows are
        always the *bit* interchange format, so any at-rest codec
        restores into any other (the ``rep`` tag records the source
        representation for restore-target defaulting)."""
        R = self._global_cols(np.asarray(self.R))
        sizes = np.asarray(self.sizes)
        keep = self._filled_host() & self._live_host
        live_count = int(keep.sum())
        return {
            "kind": np.asarray("sharded"),
            "rep": np.asarray(self.codec.kind),
            "n": np.int64(self.n),
            "count": np.int64(live_count),
            "R": (R[keep] if live_count
                  else np.zeros((0, self.n), np.uint8)),
            "sizes": (sizes[keep] if live_count
                      else np.zeros((0,), np.int32)),
            "counter": np.asarray(self.counter),
        }

    # rows staged per add_batch during restore: bounds the transient
    # single-device footprint of the host->device feed to CHUNK * n bytes
    # (the resident arena itself is born sharded and never gathers)
    RESTORE_CHUNK = 4096

    @classmethod
    def from_state(cls, st, *, mesh, theta_axes=("data",),
                   vertex_axis=None, partition=None,
                   codec: str = "bitmap") -> "ShardedStore":
        """Rebuild on ``mesh`` from any snapshot kind — ``"sharded"``
        (compact rows), ``"bitmap"`` (full-capacity arena), or encoded
        ``"packed"``/``"compressed"`` arenas (decoded to bit rows on
        host first): the valid rows are redistributed block-evenly
        across the new mesh's tiles (any theta x vertex layout) and
        re-encoded under ``codec``, and the fused counter/sizes are
        recomputed tile-locally (exactly equal to the saved ones).  Rows
        are fed in ``RESTORE_CHUNK``-row slices so an arena that only
        fits *because* it is sharded never transits any single device
        whole on restore."""
        n, rows = _live_rows_from_state(st)
        count = rows.shape[0]
        store = cls(n, mesh=mesh, theta_axes=theta_axes,
                    vertex_axis=vertex_axis, capacity=max(count, 1),
                    partition=partition, codec=codec)
        chunk = max(cls.RESTORE_CHUNK // max(store.D, 1), 1) * store.D
        slot_chunks = []
        for lo in range(0, count, chunk):
            slot_chunks.append(
                store.add_batch(jnp.asarray(rows[lo:lo + chunk], jnp.uint8)))
        # snapshot-row -> slot map for provenance trackers (row i of the
        # *live-filtered* snapshot rows landed in slot _restore_slots[i])
        store._restore_slots = (np.concatenate(slot_chunks) if slot_chunks
                                else np.zeros((0,), np.int64))
        return store


STORE_KINDS = {"bitmap": BitmapStore, "indices": IndexStore,
               "sharded": ShardedStore}

# kinds registered lazily by ``repro.core.pack`` (imported on demand so
# this module stays importable without the pack package loaded)
_PACK_KINDS = ("packed", "compressed")


def _load_pack_kinds():
    """Import the IMPack package, which registers the ``packed`` and
    ``compressed`` store kinds plus their selection strategies."""
    import repro.core.pack  # noqa: F401  (registration side effect)


def _live_rows_from_state(st) -> tuple[int, np.ndarray]:
    """Decode any snapshot kind to its live bit rows: ``(n, (count, n)
    uint8)``.  This is the cross-representation interchange path —
    bitmap / packed / compressed arenas and compact sharded rows all
    reduce to the same decoded form, which any target store's
    ``from_rows``/restore feed re-encodes."""
    kind = str(np.asarray(st["kind"]))
    n, count = int(st["n"]), int(st["count"])
    R = np.asarray(st["R"])
    if kind == "packed":
        from repro.core.pack.codec import unpack_bits_np
        rows = unpack_bits_np(R, n)
    elif kind == "compressed":
        from repro.core.pack.codec import token_decode_np
        rows = token_decode_np(R, n)
    elif kind == "indices":
        rows = np.zeros((R.shape[0], n), np.uint8)
        r, c = np.nonzero(R < n)
        rows[r, R[r, c]] = 1
    else:                       # bitmap / sharded: already bit rows
        rows = np.asarray(R, np.uint8)
    rows = rows[:count]
    if "live" in st:
        # full-arena snapshots may carry dead (stale) rows in place —
        # restore live rows only, like a compact sharded snapshot would
        rows = rows[np.asarray(st["live"])[:count].astype(bool)]
    return n, rows


def make_store(kind: str, n: int, **kw) -> RRRStore:
    """Store factory: ``"auto"`` (bitmap, the back-compat default),
    ``"bitmap"``, ``"indices"``, ``"packed"``, ``"compressed"``, or
    ``"sharded"`` (requires a ``mesh=`` keyword; accepts ``theta_axes=``
    and a ``codec=`` at-rest kind)."""
    kind = "bitmap" if kind == "auto" else kind
    if kind in _PACK_KINDS and kind not in STORE_KINDS:
        _load_pack_kinds()
    try:
        ctor = STORE_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"unknown store kind {kind!r}; have "
            f"{sorted(set(STORE_KINDS) | set(_PACK_KINDS))}")
    return ctor(n, **kw)


def _restore_error(snap_kind: str, target: str, meshed: bool) -> ValueError:
    """The one coherent restore-matrix error: names every supported
    ``(representation, mesh)`` combination instead of hinting at a
    single alternative."""
    where = "on a mesh" if meshed else "without a mesh"
    return ValueError(
        f"cannot restore a {snap_kind!r} snapshot as representation "
        f"{target!r} {where}. Supported (representation, mesh) restore "
        "combinations: 'bitmap', 'packed', and 'compressed' each restore "
        "from any 'bitmap', 'packed', 'compressed', or 'sharded' "
        "snapshot, with or without a mesh (a meshed restore builds a "
        "ShardedStore whose tiles use that at-rest codec; snapshots are "
        "decoded-row interchange, so layout none/1D/2D and at-rest "
        "format are both elastic); 'indices' restores only from an "
        "'indices' snapshot and only without a mesh (the sharded "
        "resident arena is never index-list — on meshes the C4 index "
        "representation is a derived ShardedStore.index_view, and "
        "single-device cross-representation restores re-encode, which "
        "an index-list snapshot does not round-trip). Re-run with "
        "IMMConfig(store='bitmap'/'packed'/'compressed'/'auto') for a "
        "snapshot that restores anywhere.")


def store_from_state(st, *, mesh=None, theta_axes=("data",),
                     vertex_axis=None, partition=None,
                     kind: str | None = None) -> RRRStore:
    """Rebuild a store from a `state()` tree (snapshot restore path).

    Snapshots are elastic across layouts *and* at-rest formats: bitmap,
    packed, compressed, and sharded snapshots all carry (or decode to)
    plain bit rows, so any of them restores into any target
    representation.  ``kind`` picks the target (None keeps the
    snapshot's own representation — a ``"sharded"`` snapshot's ``rep``
    tag when present, else bitmap).  With ``mesh`` given the result is a
    `ShardedStore` whose tiles use the target codec; without one it is
    the matching single-device store.  Index-list snapshots are
    single-device, same-representation only (see `_restore_error`).
    """
    snap_kind = str(np.asarray(st["kind"]))
    known = set(STORE_KINDS) | set(_PACK_KINDS)
    if snap_kind not in known:
        raise ValueError(f"snapshot has unknown store kind {snap_kind!r}")
    default = snap_kind
    if snap_kind == "sharded":
        default = str(np.asarray(st["rep"])) if "rep" in st else "bitmap"
    target = default if kind is None else kind
    if mesh is not None:
        if snap_kind == "indices" or target == "indices":
            raise _restore_error(snap_kind, target, meshed=True)
        codec = target if target in _PACK_KINDS else "bitmap"
        return ShardedStore.from_state(st, mesh=mesh, theta_axes=theta_axes,
                                       vertex_axis=vertex_axis,
                                       partition=partition, codec=codec)
    if target == "sharded":
        raise ValueError(
            "target representation 'sharded' needs a mesh= argument")
    if target == "indices" or snap_kind == "indices":
        if target == "indices" and snap_kind == "indices":
            return IndexStore.from_state(st)
        raise _restore_error(snap_kind, target, meshed=False)
    if target in _PACK_KINDS:
        _load_pack_kinds()
    if target == snap_kind:
        # same representation, full-arena snapshot: restore in place
        return STORE_KINDS[target].from_state(st)
    n, rows = _live_rows_from_state(st)
    return STORE_KINDS[target].from_rows(rows, n)
