"""Pallas TPU kernels: greedy selection's counts over *listed* arena rows.

After its first counter build, greedy max coverage changes the counter
only through the sets a pick newly covers (or, once they outnumber the
survivors, through the survivors), so every later count is over a short
list of rows.  The at-rest ``(theta, n)`` uint8 arena cannot serve rows:
its layout is vertex-major (``{0,1}``), so one set's bytes are strided
over the whole arena.  Two kernels:

* `row_view` reads the arena once, in place (``R.T`` is a bitcast of the
  at-rest layout), and writes a set-major *bit-packed* copy ``W (theta,
  C, 128)`` int32 in which each row is one contiguous slab, 32 vertices
  a word (which vertex each bit is: `vertex_ids`), zero past ``n``.  In
  the same pass it counts the rows of ``valid`` on the MXU (the first
  counter build), so the build reads nothing more.
* `coverage_rows` counts the rows of ``W`` that a mask lists: a grid of
  one step per listed row, each DMAing its row whole, so a call reads
  the listed rows and no others, whatever the arena's height (the row
  ids come from one sort of ``theta`` keys, the listed rows first).
  Rows add into an 8-plane bit-sliced counter (a ripple of XOR and AND
  a word), decoded to int32 every 255 rows, so no count overflows.

Counts come back as planes ``(32, C, 128) int32``, the count of the
vertex bit ``k`` of word ``(c, l)`` holds at ``[k, c, l]`` (`vertex_ids`
names each entry); vertices past ``n`` count 0.  Counts are exact for
0/1 arenas (the store's bitmaps).  The copy is an eighth of the arena's
bytes, so the call's one full pass is a read.  Blocks on v5e: the arena
in ``(32768, 128)`` uint8 vertex x set tiles, packed in int32 and
transposed on the XLU; a row of ``W`` is ``C x 128`` words, C a
multiple of 8.  ``tests/test_tpu_compile.py`` compiles both at
com-Amazon's width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_VERTS = 32768       # vertices per arena tile: 1024 words, 8 x 128 of W
_SETS = 128          # sets per arena tile
_PLANES = 8          # bit-sliced counter planes: counts to 255
_FLUSH = (1 << _PLANES) - 1


def words_per_row(n: int) -> int:
    """C, the 128-word groups of a `row_view` row for ``n`` vertices."""
    return pl.cdiv(n, _VERTS) * (_VERTS // 32 // _LANES)


def vertex_ids(c: int):
    """(32, C, 128) int32: the vertex each entry of a count's planes is.
    Word ``w = 128 c + l`` of a row packs, from arena tile ``w // 1024``,
    the vertices ``4 (w % 1024) + 4096 i + b`` in bit ``k = 4 i + b``."""
    k, c, l = (jax.lax.broadcasted_iota(jnp.int32, (32, c, _LANES), d)
               for d in range(3))
    w = _LANES * c + l
    return (_VERTS * (w // 1024) + 4096 * (k // 4) + 4 * (w % 1024)
            + k % 4)


def _bit(words, k):
    return jax.lax.shift_right_logical(words, k) & 1


# ------------------------------------------------------------ row_view ----

def _nibbles(quads):
    """Four 0/1 bytes a word -> their four bits: byte ``b`` of the word
    lands on bit ``21 + b`` of its product with 0x204081 (no other
    product term reaches bits 21 to 24)."""
    return jax.lax.shift_right_logical(quads * 0x204081, 21) & 0xF


def _view_kernel(valid_ref, x_ref, w_ref, cnt_ref, *, n: int):
    v, t = pl.program_id(0), pl.program_id(1)
    vb, tt = x_ref.shape

    @pl.when(t == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    quads = pltpu.bitcast(x_ref[...], jnp.int32)          # (vb // 4, tt)

    def emit(quads):
        # word p packs quads p + 1024 i, i < 8: whole vregs, no shuffles
        nibbles = _nibbles(quads).reshape(8, vb // 32, tt)
        words = sum(nibbles[i] << (4 * i) for i in range(8))
        w_ref[...] = words.T.reshape(w_ref.shape)

    last = pl.num_programs(0) - 1

    @pl.when(v < last)
    def _inner():
        emit(quads)

    @pl.when(v == last)
    def _edge():
        # bytes of vertices past n come from past the arena: zero them
        quad = jax.lax.broadcasted_iota(jnp.int32, quads.shape, 0)
        first = v * vb + 4 * quad
        keep = sum(jnp.where(first + b < n, 0xFF << (8 * b), 0)
                   for b in range(3))
        keep = keep | jnp.where(first + 3 < n, jnp.int32(-(1 << 24)), 0)
        emit(quads & keep)

    # the counter build on the MXU: 0/1 bytes are int8 as they are
    counts = jax.lax.dot_general(
        valid_ref[...], pltpu.bitcast(x_ref[...], jnp.int8),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
    vertex = v * vb + jax.lax.broadcasted_iota(jnp.int32, counts.shape, 1)
    cnt_ref[...] += jnp.where(vertex < n, counts, 0).reshape(cnt_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def row_view(R, valid, *, interpret: bool = False):
    """R: (theta, n) uint8 0/1 arena, valid: (theta,) bool -> (W (theta,
    C, 128) int32 set-major bit-packed rows, the count of the valid rows
    as (32, C, 128) int32 planes), in one pass over the arena."""
    theta, n = R.shape
    c = words_per_row(n)
    nv, nt = pl.cdiv(n, _VERTS), pl.cdiv(theta, _SETS)
    # zero past theta: those lanes of the last set tile lie past the arena
    valid2 = jnp.zeros((1, nt * _SETS), jnp.int8).at[0, :theta].set(
        valid.astype(jnp.int8))
    W, counts = pl.pallas_call(
        functools.partial(_view_kernel, n=n),
        grid=(nv, nt),
        in_specs=[
            pl.BlockSpec((1, _SETS), lambda v, t: (0, t)),
            pl.BlockSpec((_VERTS, _SETS), lambda v, t: (v, t)),
        ],
        out_specs=[
            pl.BlockSpec((_SETS, c // nv, _LANES), lambda v, t: (t, v, 0)),
            pl.BlockSpec((_VERTS // _LANES, _LANES), lambda v, t: (v, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((theta, c, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((nv * _VERTS // _LANES, _LANES),
                                 jnp.int32),
        ],
        interpret=interpret,
    )(valid2, R.T)
    # vertex order -> the planes' order (`vertex_ids`), once a call
    planes = counts.reshape(nv, 8, 1024, 4).transpose(1, 3, 0, 2)
    return W, planes.reshape(32, c, _LANES)


# -------------------------------------------------------- coverage_rows ----

def _rows_kernel(ids_ref, w_ref, out_ref, planes_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        planes_ref[...] = jnp.zeros_like(planes_ref)

    carry = w_ref[...]
    for i in range(_PLANES):          # add the row's bits: a ripple carry
        plane = planes_ref[i]
        planes_ref[i] = plane ^ carry
        carry = plane & carry

    @pl.when((j % _FLUSH == _FLUSH - 1) | (j == pl.num_programs(0) - 1))
    def _flush():
        planes = [planes_ref[i] for i in range(_PLANES)]
        for k in range(32):
            out_ref[k] += sum(_bit(p, k) << i for i, p in enumerate(planes))
        planes_ref[...] = jnp.zeros_like(planes_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def coverage_rows(listed, W, *, interpret: bool = False):
    """Count planes ``(32, C, 128) int32`` of the rows of a `row_view`
    arena ``W`` that ``listed (theta,) bool`` marks.  The grid has one
    step per listed row, which it DMAs whole: a call reads just the
    listed rows, none when there are none."""
    theta, c, lanes = W.shape
    # the listed rows first, in order: one sort of theta keys
    rows = jnp.arange(theta, dtype=jnp.int32)
    ids = jnp.sort(jnp.where(listed, rows, theta + rows))
    count = listed.sum(dtype=jnp.int32)

    def run(ids, count):
        return pl.pallas_call(
            _rows_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(count,),
                in_specs=[pl.BlockSpec((None, c, lanes),
                                       lambda j, ids: (ids[j], 0, 0))],
                out_specs=pl.BlockSpec((32, c, lanes),
                                       lambda j, ids: (0, 0, 0)),
                scratch_shapes=[pltpu.VMEM((_PLANES, c, lanes), jnp.int32)],
            ),
            out_shape=jax.ShapeDtypeStruct((32, c, lanes), jnp.int32),
            interpret=interpret,
        )(ids, W)

    return jax.lax.cond(
        count > 0, run, lambda *_: jnp.zeros((32, c, lanes), jnp.int32),
        ids, count)
