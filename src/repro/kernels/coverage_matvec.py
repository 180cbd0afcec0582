"""Pallas TPU kernel: EfficientIMM counter rebuild ``counter = alive @ R``.

The RRRset bitmap block streams HBM->VMEM tile by tile and the masked
mat-vec runs on the MXU; the theta axis is the minor grid dimension so the
output tile accumulates in place across theta tiles (revisited output block —
the canonical TPU accumulation pattern).

Block shapes on v5e: alive (1, Tt), R (Tt, Tn) uint8, out (1, Tn) f32,
with Tt = 256 and Tn = 512 (or the full dimension when smaller) — the
8-bit arena tile is (32, 128)-aligned and the lane axis of every block is
a multiple of 128.  The arena is never padded or copied: a partial last
column block only feeds output columns past ``n``, which are sliced off,
and rows past ``theta`` meet zero-padded ``alive`` entries.  The uint8
tile is widened through int32 (Mosaic has no direct uint8 -> f32 cast).
``tests/test_tpu_compile.py`` compiles this kernel for a v5e at
com-Amazon's width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _pad


DEFAULT_TILE_THETA = 256
DEFAULT_TILE_N = 512


def _kernel(alive_ref, r_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    r = r_ref[...].astype(jnp.int32).astype(jnp.float32)     # (Tt, Tn)
    out_ref[...] += jnp.dot(alive_ref[...], r,
                            preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("tile_theta", "tile_n", "interpret"))
def coverage_matvec(alive, R, *, tile_theta: int = DEFAULT_TILE_THETA,
                    tile_n: int = DEFAULT_TILE_N, interpret: bool = False):
    """alive: (theta,) f32/bool; R: (theta, n) uint8 -> (n,) f32 counter."""
    theta, n = R.shape
    tt = min(tile_theta, theta)
    tn = min(tile_n, n)
    grid = (pl.cdiv(n, tn), pl.cdiv(theta, tt))
    # zero alive past theta: those rows of the last theta block are
    # whatever lies past the arena and must contribute nothing
    alive2 = _pad.pad_to(alive.astype(jnp.float32), 0, tt)[None, :]
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tt), lambda i, j: (0, j)),
            pl.BlockSpec((tt, tn), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, tn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, grid[0] * tn), jnp.float32),
        interpret=interpret,
    )(alive2, R)
    return out[0, :n]
