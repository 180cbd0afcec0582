"""Pallas TPU kernel: OR of contiguous row segments — the pull step's
reduction of the sparse IC traversal.

The sparse sampler holds its state with vertices on rows and the batch
on lanes, and its edges sorted by source (CSR order), so the rows each
vertex ORs together — the live bits of its out-edges — are one
contiguous run.  ``out[u] = OR of live[off[u]:off[u+1]]``.

The kernel walks a list of (vertex block, edge tile) work items: a
vertex block of ``tile_v`` consecutive vertices reads every ``tile_e``-row
tile its edge range touches, and accumulates ``onehot (tile_v, tile_e)
@ live (tile_e, B)`` on the MXU in int8 with an int32 accumulator
(exact), where ``onehot[r, j]`` says edge ``j`` leaves vertex ``r`` of
the block.  Items of one block are consecutive, so its ``(tile_v, B)``
output block stays resident across them and is written once.  Only the
item list depends on the graph, so it is built
in-jit from the sorted sources with a length that ``(n, m)`` bounds: a
block's tiles overlap its neighbours' by at most one, so ``ceil(m /
tile_e) + ceil(n / tile_v)`` items suffice, and the spare items repeat
the last block's last tile (an OR is idempotent).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TILE_V = 256
DEFAULT_TILE_E = 1024


def _kernel(vb_ref, tile_ref, src_ref, live_ref, out_ref, acc_ref):
    i = pl.program_id(0)
    last_i = pl.num_programs(0) - 1
    vb = vb_ref[i]
    first = (i == 0) | (vb_ref[jnp.maximum(i - 1, 0)] != vb)
    last = (i == last_i) | (vb_ref[jnp.minimum(i + 1, last_i)] != vb)
    tv = acc_ref.shape[0]

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    src = src_ref[0]                                    # (1, tile_e)
    rows = (jax.lax.broadcasted_iota(jnp.int32, (tv, src.shape[1]), 0)
            + vb * tv)
    onehot = (rows == src).astype(jnp.int8)
    acc_ref[...] += jnp.dot(onehot, live_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(last)
    def _write():
        out_ref[...] = (acc_ref[...] > 0).astype(out_ref.dtype)


def work_items(src, n: int, tile_v: int, tile_e: int):
    """``(vertex block, edge tile)`` of each work item, ``(items,)``
    int32 each: the tiles each block's edge range touches, blocks in
    order, padded with repeats of the last item."""
    m = src.shape[0]
    nb, nt = pl.cdiv(n, tile_v), pl.cdiv(m, tile_e)
    off = jnp.searchsorted(src, jnp.arange(nb + 1, dtype=src.dtype) * tile_v
                           ).astype(jnp.int32)
    first = jnp.minimum(off[:-1] // tile_e, nt - 1)
    lastt = jnp.maximum(first, (off[1:] - 1) // tile_e)
    count = lastt - first + 1
    start = jnp.cumsum(count) - count
    items = nt + nb
    i = jnp.arange(items, dtype=jnp.int32)
    vb = jnp.searchsorted(start, i, side="right").astype(jnp.int32) - 1
    tile = first[vb] + (i - start[vb])
    over = i >= start[-1] + count[-1]
    return (jnp.where(over, nb - 1, vb).astype(jnp.int32),
            jnp.where(over, lastt[-1], tile).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n", "tile_v", "tile_e",
                                             "interpret"))
def segment_or(live, src, *, n: int, tile_v: int = DEFAULT_TILE_V,
               tile_e: int = DEFAULT_TILE_E, interpret: bool = False):
    """live: (m, B) 0/1 rows in source order; src: (m,) int32 sorted
    sources.  Returns ``(n, B) bool``: row ``u`` is the OR of the rows
    whose source is ``u`` (False where ``u`` has none)."""
    m, B = live.shape
    nb, nt = pl.cdiv(n, tile_v), pl.cdiv(m, tile_e)
    vb, tile = work_items(src, n, tile_v, tile_e)
    # the last tile's rows past m are read unspecified: their source -1
    # matches no vertex, and an int8 row times a zero one-hot column adds
    # exactly nothing
    srcp = jnp.pad(src.astype(jnp.int32), (0, nt * tile_e - m),
                   constant_values=-1)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(vb.shape[0],),
            in_specs=[
                pl.BlockSpec((1, 1, tile_e), lambda i, v, t: (t[i], 0, 0)),
                pl.BlockSpec((tile_e, B), lambda i, v, t: (t[i], 0)),
            ],
            out_specs=pl.BlockSpec((tile_v, B), lambda i, v, t: (v[i], 0)),
            scratch_shapes=[pltpu.VMEM((tile_v, B), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nb * tile_v, B), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(vb, tile, srcp.reshape(nt, 1, tile_e), live.astype(jnp.int8))
    return out[:n].astype(jnp.bool_)
