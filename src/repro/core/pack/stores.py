"""Single-device compressed-at-rest RRR arenas behind the `RRRStore`
protocol.

`PackedBitmapStore` and `CompressedStore` are one arena class
(`CodecStore`) parameterized by the at-rest codec: rows arrive as
``(B, n) uint8`` bitmaps, are encoded on write (fused pack-on-write —
one donated jit does encode + dynamic_update_slice), and all reads
(counting, hits, index conversion, stream reverse-touch) decode on the
fly, so the logical ``(theta, n)`` arena never rests in memory.  Under a
`StorePressurePolicy` with a ``ladder``, an over-cap arena first morphs
its codec down the ladder (packed -> compressed) before any live row is
evicted — `_compress_step` swaps ``codec``/``R`` in place and the store
keeps its class, so ``representation`` follows ``codec.kind``.

The dense `BitmapStore` itself never morphs (its class is its layout);
the single-device ladder therefore starts at `PackedBitmapStore`, while
`ShardedStore` covers the full bitmap -> packed -> compressed ladder by
swapping per-tile codecs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.adaptive import bitmap_to_indices
from repro.core.pack.codec import (
    MIN_TOKEN_PAD,
    TokenCodec,
    codec_for,
    tokens_needed,
)
from repro.core.store import (
    MIN_CAPACITY,
    StoreView,
    _ArenaBase,
    _ladder_next,
    _restore_live,
    _write_rows,
    next_pow2,
)
from repro.kernels import ops


@partial(jax.jit, static_argnames=("codec",), donate_argnums=(0,))
def _encode_write(arena, bits, start, *, codec):
    """Fused pack-on-write: encode the bit batch and splice it into the
    (donated) arena at dynamic row offset ``start`` in one jit."""
    return jax.lax.dynamic_update_slice(
        arena, codec.encode(bits), (start, jnp.int32(0)))


@partial(jax.jit, static_argnames=("codec_from", "codec_to"))
def _recode(arena, *, codec_from, codec_to):
    """Whole-arena codec morph (the pressure-ladder step): decode under
    the old codec, re-encode under the new one.  The decoded bits are a
    jit temporary — they never rest."""
    return codec_to.encode(codec_from.decode(arena))


@partial(jax.jit, static_argnames=("codec",))
def _codec_hits(R, valid, S, *, codec):
    """`_bitmap_hits` semantics on an encoded arena: per-query covered
    fraction via ``decode_cols`` membership (lax.map bounds the decoded
    broadcast to one query at a time)."""
    n_valid = jnp.maximum(valid.sum(dtype=jnp.float32), 1.0)

    def one(s):
        memb = codec.decode_cols(R, s).any(axis=-1)
        return (memb & valid).sum(dtype=jnp.int32)

    return jax.lax.map(one, S).astype(jnp.float32) / n_valid


class CodecStore(_ArenaBase):
    """Single-device encoded arena: ``(capacity, codec.width)`` of
    ``codec.dtype``.  See the module docstring; use the
    `PackedBitmapStore` / `CompressedStore` aliases to pick the initial
    codec."""

    _initial_kind = "packed"

    def __init__(self, n: int, *, capacity: int = MIN_CAPACITY,
                 policy=None, s_pad: int = MIN_TOKEN_PAD):
        super().__init__(n, capacity=capacity, policy=policy)
        self.codec = codec_for(self._initial_kind, self.n,
                               s_pad=next_pow2(s_pad, MIN_TOKEN_PAD))
        self.R = jnp.full((self.capacity, self.codec.width),
                          self._fill_value(), self.codec.dtype)
        self._idx_cache = None      # (version, l_pad) -> R_idx

    @property
    def representation(self) -> str:
        return self.codec.kind

    # ------------------------------------------------- arena base hooks ----

    def _realloc(self, new_cap: int):
        R = jnp.full((new_cap, self.codec.width), self._fill_value(),
                     self.codec.dtype)
        self.R = _write_rows(R, self.R, jnp.int32(0))

    def _row_bytes(self) -> int:
        # physical at-rest bytes per row — this is what the pressure
        # policy caps and what the obs byte gauges report
        return self.codec.width * jnp.dtype(self.codec.dtype).itemsize

    def _fill_value(self):
        return jnp.asarray(self.codec.fill, self.codec.dtype)

    def _rows_for_storage(self, rows):
        if isinstance(self.codec, TokenCodec):
            self._widen_tokens(int(tokens_needed(rows).max()))
        return self.codec.encode(rows)

    def _row_contrib(self, mask):
        # decode-and-count through the kernels/ops dispatch (jnp oracle
        # off-TPU, Pallas on TPU) — exact: integer counts in f32
        return ops.arena_count(self.R, mask,
                               codec=self.codec).astype(jnp.int32)

    def _compress_step(self) -> bool:
        ladder = self.policy.ladder if self.policy is not None else ()
        nxt = _ladder_next(self.codec.kind, ladder)
        if nxt is None:
            return False
        if nxt == "compressed":
            # token width covering every resident row (fill rows decode
            # to all-zero bits and need 0 tokens)
            need = int(jnp.max(tokens_needed(self.codec.decode(self.R)),
                               initial=0))
            new_codec = codec_for(nxt, self.n,
                                  s_pad=next_pow2(max(need, 1),
                                                  MIN_TOKEN_PAD))
        else:
            new_codec = codec_for(nxt, self.n)
        self.R = _recode(self.R, codec_from=self.codec, codec_to=new_codec)
        self.codec = new_codec
        self.version += 1
        obs.counter("store.compress_steps").add(1)
        return True

    def _widen_tokens(self, s_need: int):
        new_s = next_pow2(s_need, self.codec.s_pad)
        if new_s == self.codec.s_pad:
            return
        pad = jnp.full((self.capacity, new_s - self.codec.s_pad),
                       self._fill_value(), self.codec.dtype)
        self.R = jnp.concatenate([self.R, pad], axis=1)
        self.codec = TokenCodec(self.n, new_s)
        self.version += 1

    # -------------------------------------------------------- RRR store ----

    def add_batch(self, visited, counter=None) -> np.ndarray:
        with obs.span("store.write", tier="store", kind=self.codec.kind):
            visited = jnp.asarray(visited).astype(jnp.uint8)
            B = int(visited.shape[0])
            batch_sizes = visited.sum(axis=1, dtype=jnp.int32)
            if isinstance(self.codec, TokenCodec):
                self._widen_tokens(int(tokens_needed(visited).max()))
            self._ensure_room(B)
            self._grow_rows(self.count + B)
            if counter is None:
                counter = visited.sum(axis=0, dtype=jnp.int32)
            slots = np.arange(self.count, self.count + B, dtype=np.int64)
            self.R = _encode_write(self.R, visited, jnp.int32(self.count),
                                   codec=self.codec)
            self._finish_add(batch_sizes, counter)
        return slots

    def view(self) -> StoreView:
        return StoreView(self.representation, self.R, self._valid(),
                         self.n, self.count)

    def index_view(self, l_pad: int) -> StoreView:
        """Lazy C4 conversion (decode is a jit temporary); cached until
        the arena next changes."""
        key = (self.version, int(l_pad))
        if self._idx_cache is None or self._idx_cache[0] != key:
            R_idx = jax.jit(
                lambda R: bitmap_to_indices(self.codec.decode(R),
                                            int(l_pad)))(self.R)
            self._idx_cache = (key, R_idx)
        return StoreView("indices", self._idx_cache[1], self._valid(),
                         self.n, self.count)

    def hits(self, S) -> jnp.ndarray:
        with obs.span("count", tier="store", kind=self.codec.kind):
            return _codec_hits(self.R, self._valid(),
                               jnp.asarray(S, jnp.int32), codec=self.codec)

    def state(self) -> dict:
        """Host snapshot: the *encoded* arena plus counters; kind tag is
        the codec kind (``"packed"``/``"compressed"``)."""
        st = self._base_state()
        st["kind"] = np.asarray(self.codec.kind)
        st["R"] = np.asarray(self.R)
        return st

    @classmethod
    def from_state(cls, st) -> "CodecStore":
        kind = str(st["kind"])
        R = np.asarray(st["R"])
        store = cls.__new__(cls)
        _ArenaBase.__init__(store, int(st["n"]), capacity=R.shape[0])
        store.codec = (codec_for(kind, store.n, s_pad=R.shape[1])
                       if kind == "compressed"
                       else codec_for(kind, store.n))
        store._idx_cache = None
        store.R = jnp.asarray(R, store.codec.dtype)
        store.sizes = jnp.asarray(st["sizes"], jnp.int32)
        store.counter = jnp.asarray(st["counter"], jnp.int32)
        store.count = int(st["count"])
        _restore_live(store, st)
        return store

    @classmethod
    def from_rows(cls, rows, n: int, *, policy=None) -> "CodecStore":
        """Build a store holding exactly ``rows (count, n) uint8`` bit
        rows — the cross-layout restore path.  ``_restore_slots`` maps
        snapshot row -> slot for streaming provenance."""
        store = cls(int(n), capacity=max(int(rows.shape[0]), MIN_CAPACITY),
                    policy=policy)
        if rows.shape[0]:
            store._restore_slots = store.add_batch(
                jnp.asarray(rows, jnp.uint8))
        else:
            store._restore_slots = np.zeros((0,), np.int64)
        return store


class PackedBitmapStore(CodecStore):
    """Bit-packed arena: ``(capacity, ceil(n/8)) uint8`` — 8x smaller at
    rest than `BitmapStore`, bitwise-identical in every answer."""
    _initial_kind = "packed"


class CompressedStore(CodecStore):
    """Compressed-at-rest arena: per-row literal/run token lists
    (``(capacity, s_pad) int32``), decode-and-count on every read."""
    _initial_kind = "compressed"


# register with the store factory (engine imports repro.core.pack;
# make_store/store_from_state lazy-import it)
from repro.core import store as _store_mod  # noqa: E402

_store_mod.STORE_KINDS["packed"] = PackedBitmapStore
_store_mod.STORE_KINDS["compressed"] = CompressedStore
