"""Pallas TPU kernel: fused arena commit — encode + per-vertex count in
one pass over a sampled batch.

The tail of the sample->write->count chain (PR 10).  The traversal loop's
final ``visited (B, n)`` block is consumed tile by tile: each tile is
converted to its at-rest form (identity for bitmap arenas, LSB-first
8-bits-per-byte packing for packed arenas — the MXU does the packing as a
structured mat-mul against a {0, 2^j} weight matrix) and its per-vertex
column sum is accumulated into the fused counter contribution in the same
VMEM residency.  Unfused, the store's write path re-reads the batch from
HBM once to encode and once to count; fused, the batch block streams
HBM->VMEM exactly once.

Grid: ``(col_tiles, row_tiles)`` with rows minor, so the ``(1, Tn)``
counter output block is revisited across row tiles and accumulates in
place (the canonical TPU accumulation pattern).  Zero row/column padding
is neutral for both outputs: padded bits pack to zero bytes and add zero
to every column count — exactly what `repro.core.pack.codec.pack_bits`
does with a non-multiple-of-8 width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _pad


DEFAULT_TILE_ROWS = 128
DEFAULT_TILE_N = 512
DEFAULT_TILE_BYTES = 128


def _bitmap_kernel(rows_ref, stored_ref, colsum_ref):
    r = pl.program_id(1)
    rows = rows_ref[...]
    stored_ref[...] = rows

    @pl.when(r == 0)
    def _init():
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    colsum_ref[...] += rows.astype(jnp.int32).sum(axis=0, keepdims=True)


def _packed_kernel(rows_ref, stored_ref, colsum_ref):
    r = pl.program_id(1)
    rows = rows_ref[...]                                # (Tb, 8 * Tw) 0/1
    tw8 = rows.shape[1]
    tw = tw8 // 8
    # byte j of the tile is sum_i bits[8j + i] << i: a structured matmul
    # against W[c, j] = 2^(c % 8) * [c // 8 == j] — exact in f32 (<= 255)
    cc = jax.lax.broadcasted_iota(jnp.int32, (tw8, tw), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (tw8, tw), 1)
    weights = jnp.where(cc // 8 == jj,
                        jnp.left_shift(1, cc % 8), 0).astype(jnp.float32)
    ri = rows.astype(jnp.int32)
    packed = jnp.dot(ri.astype(jnp.float32), weights,
                     preferred_element_type=jnp.float32)
    stored_ref[...] = packed.astype(jnp.int32).astype(jnp.uint8)

    @pl.when(r == 0)
    def _init():
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    colsum_ref[...] += ri.sum(axis=0, keepdims=True)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "tile_rows", "tile_n", "tile_bytes",
                     "interpret"))
def arena_commit(rows, *, kind: str = "bitmap",
                 tile_rows: int = DEFAULT_TILE_ROWS,
                 tile_n: int = DEFAULT_TILE_N,
                 tile_bytes: int = DEFAULT_TILE_BYTES,
                 interpret: bool = False):
    """rows: (B, n) uint8/bool 0/1 membership rows.

    Returns ``(stored, colsum)`` where ``stored`` is the at-rest block —
    ``(B, n) uint8`` for ``kind="bitmap"``, ``(B, ceil(n/8)) uint8``
    LSB-first packed bytes for ``kind="packed"`` (bitwise-equal to
    ``pack_bits``) — and ``colsum (n,) int32`` is the batch's fused
    per-vertex counter contribution.
    """
    rows = rows.astype(jnp.uint8)
    B, n = rows.shape
    tb = min(tile_rows, B)
    if kind == "bitmap":
        tn = min(tile_n, n)
        rowsp = _pad.pad_to(_pad.pad_to(rows, 0, tb), 1, tn)
        nc, nr = pl.cdiv(n, tn), pl.cdiv(B, tb)
        stored, colsum = pl.pallas_call(
            _bitmap_kernel,
            grid=(nc, nr),
            in_specs=[pl.BlockSpec((tb, tn), lambda c, r: (r, c))],
            out_specs=[
                pl.BlockSpec((tb, tn), lambda c, r: (r, c)),
                pl.BlockSpec((1, tn), lambda c, r: (0, c)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(rowsp.shape, jnp.uint8),
                jax.ShapeDtypeStruct((1, rowsp.shape[1]), jnp.int32),
            ],
            interpret=interpret,
        )(rowsp)
        return stored[:B, :n], colsum[0, :n]
    if kind != "packed":
        raise ValueError(f"arena_commit kind must be bitmap|packed, "
                         f"got {kind!r}")
    W = -(-n // 8)
    tw = min(tile_bytes, W)
    tw8 = tw * 8
    rowsp = _pad.pad_to(_pad.pad_to(rows, 0, tb), 1, tw8)
    nc, nr = rowsp.shape[1] // tw8, pl.cdiv(B, tb)
    stored, colsum = pl.pallas_call(
        _packed_kernel,
        grid=(nc, nr),
        in_specs=[pl.BlockSpec((tb, tw8), lambda c, r: (r, c))],
        out_specs=[
            pl.BlockSpec((tb, tw), lambda c, r: (r, c)),
            pl.BlockSpec((1, tw8), lambda c, r: (0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rowsp.shape[0], nc * tw), jnp.uint8),
            jax.ShapeDtypeStruct((1, rowsp.shape[1]), jnp.int32),
        ],
        interpret=interpret,
    )(rowsp)
    return stored[:B, :W], colsum[0, :n]
