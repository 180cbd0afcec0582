"""Pallas TPU kernel: fused counter rebuild + arg-max (paper C3 applied to
Find_Most_Influential_Set).

One greedy round = mat-vec + global arg-max.  Unfused, the (n,) counter
round-trips HBM between the two; fused, each counter tile lives only in a
VMEM scratch accumulator and is reduced to a per-tile (max, argmax) pair the
moment its theta accumulation completes.  The tiny per-tile pairs are
reduced in jnp by the wrapper.

Block shapes on v5e: alive (1, Tt), R (Tt, Tn) uint8 as in
`coverage_matvec`; each tile writes its (max, argmax) pair broadcast over
one lane-aligned ``(1, 128)`` output block, and the wrapper reads lane 0
of each.  Columns past ``n`` in the last tile are masked out of the race
inside the kernel.  ``tests/test_tpu_compile.py`` compiles this kernel
for a v5e at com-Amazon's width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _pad

_LANES = 128


def _kernel(alive_ref, r_ref, max_ref, idx_ref, acc_ref, *, n: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r = r_ref[...].astype(jnp.int32).astype(jnp.float32)
    acc_ref[...] += jnp.dot(alive_ref[...], r,
                            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _reduce():
        tn = acc_ref.shape[1]
        cols = i * tn + jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1)
        c = jnp.where(cols < n, acc_ref[...], -1.0)      # counts are >= 0
        best = jnp.max(c)
        # first column holding the max: jnp.argmax's tie-break
        first = jnp.min(jnp.where(c == best, cols, jnp.int32(2 ** 30)))
        max_ref[...] = jnp.full(max_ref.shape, best, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, first, jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("tile_theta", "tile_n", "interpret"))
def fused_select(alive, R, *, tile_theta: int = 256, tile_n: int = 512,
                 interpret: bool = False):
    """-> (max_count () f32, argmax () int32) over counter = alive @ R."""
    theta, n = R.shape
    tt = min(tile_theta, theta)
    tn = min(tile_n, n)
    alive2 = _pad.pad_to(alive.astype(jnp.float32), 0, tt)[None, :]
    ni, nj = pl.cdiv(n, tn), pl.cdiv(theta, tt)
    maxs, idxs = pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid=(ni, nj),
        in_specs=[
            pl.BlockSpec((1, tt), lambda i, j: (0, j)),
            pl.BlockSpec((tt, tn), lambda i, j: (j, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, _LANES), lambda i, j: (0, i)),
            pl.BlockSpec((1, _LANES), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, ni * _LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, ni * _LANES), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, tn), jnp.float32)],
        interpret=interpret,
    )(alive2, R)
    maxs, idxs = maxs[0, ::_LANES], idxs[0, ::_LANES]
    best_tile = jnp.argmax(maxs)       # tiles ascend, so first max wins
    return maxs[best_tile], idxs[best_tile]
