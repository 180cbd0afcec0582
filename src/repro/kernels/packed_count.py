"""Pallas TPU kernels: decode-and-count over encoded RRR arenas.

The IMPack counting path (HBMax direction): arenas rest bit-packed
(8 vertices per byte) or token-compressed (per-row literal/run token
lists over the packed bytes — see ``repro.core.pack.codec``), and the
greedy counter rebuild ``counter[v] = #alive sets containing v`` decodes
*inside* the kernel, so the logical ``(theta, n)`` uint8 arena never
materializes in HBM.

`packed_count` — grid ``(n_byte_tiles, row_tiles)`` with rows as the
contraction (minor) axis: each step unpacks a ``(Tr, Tb)`` byte tile to
``(Tr, Tb*8)`` bits with shift/mask ops on the VPU and accumulates
``alive_tile @ bits`` on the MXU into VMEM scratch; the epilogue writes
the column tile once on the last row step.

`token_count` — grid ``(col_tiles, row_tiles)``: each step rebuilds the
``(Tr, Tn)`` bit tile from the rows' token lists by comparing token
blocks against the tile's column ids (literal tokens contribute their
byte's bit, run tokens cover their 32-byte superblock; the sentinel's
code 0 never sets a bit), OR-reducing over the token axis in chunks to
bound the broadcast, then accumulates the same masked matmul.

Both return exact integer counts (f32 accumulation of 0/1 products);
``interpret=True`` validates on CPU against the jnp oracles in
``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _pad

_SB = 32        # token superblock: bytes per saturated-run token
_BASE_LOG2 = 9
_BASE = 1 << _BASE_LOG2   # token = block * _BASE + code
_SAT = 256      # code marking a saturated run
_LANES = 128


def _packed_kernel(alive_ref, packed_ref, out_ref, acc_ref):
    rr = pl.program_id(1)
    nr = pl.num_programs(1)

    @pl.when(rr == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bytes_ = packed_ref[...].astype(jnp.int32)              # (Tr, Tb)
    # bit-major accumulator: row i counts bit i of every byte column, so
    # no lane-interleaving reshape is needed inside the kernel
    for i in range(8):
        bits = ((bytes_ >> i) & 1).astype(jnp.float32)
        acc_ref[i:i + 1, :] += jnp.dot(alive_ref[...], bits,
                                       preferred_element_type=jnp.float32)

    @pl.when(rr == nr - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("n", "tile_r", "tile_b", "interpret"))
def packed_count(packed, alive, *, n: int, tile_r: int = 256,
                 tile_b: int = 512, interpret: bool = False):
    """packed: (theta, ceil(n/8)) uint8, alive: (theta,) f32/bool ->
    counter (n,) int32."""
    theta, nb = packed.shape
    tr, tb = min(tile_r, max(theta, 1)), min(tile_b, nb)
    # the arena is read in place: bytes past nb only feed output columns
    # that are sliced off, and rows past theta meet zero-padded alive
    ap = _pad.pad_to(alive.astype(jnp.float32).reshape(1, -1), 1, tr)
    grid = (pl.cdiv(nb, tb), pl.cdiv(theta, tr))
    out = pl.pallas_call(
        _packed_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tr), lambda j, r: (0, r)),
            pl.BlockSpec((tr, tb), lambda j, r: (r, j)),
        ],
        out_specs=pl.BlockSpec((8, tb), lambda j, r: (0, j)),
        out_shape=jax.ShapeDtypeStruct((8, grid[0] * tb), jnp.int32),
        scratch_shapes=[pltpu.VMEM((8, tb), jnp.float32)],
        interpret=interpret,
    )(ap, packed)
    # counter[8 * byte + bit] = out[bit, byte]
    return out[:, :nb].T.reshape(-1)[:n]


def _token_kernel(alive_ref, tokens_ref, out_ref, acc_ref):
    rr = pl.program_id(1)
    nr = pl.num_programs(1)

    @pl.when(rr == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tr, s_pad = tokens_ref.shape
    tn = out_ref.shape[-1]
    cols = (pl.program_id(0) * tn
            + jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1))
    cblk = cols >> 3                                        # (1, Tn)
    cbit = cols & 7
    csb = cblk & ~(_SB - 1)

    def one_token(t, hit):
        # one (Tr, 1) token column against the tile's (1, Tn) column ids:
        # every operand stays 2D, broadcast along lanes or sublanes
        blk = t >> _BASE_LOG2
        code = t & (_BASE - 1)
        lit = ((code < _SAT) & (blk == cblk)
               & (((code >> cbit) & 1) > 0))
        sat = (code == _SAT) & (blk == csb)
        return hit | (lit | sat).astype(jnp.int32)

    def chunk(toks, hit):
        for j in range(toks.shape[1]):
            hit = one_token(toks[:, j:j + 1], hit)
        return hit

    hit = jnp.zeros((tr, tn), jnp.int32)
    if s_pad <= _LANES:
        hit = chunk(tokens_ref[...], hit)
    else:
        # wider token rows are read in 128-lane chunks (Mosaic needs
        # lane-aligned dynamic offsets; s_pad is a power of two)
        hit = jax.lax.fori_loop(
            0, s_pad // _LANES,
            lambda c, h: chunk(tokens_ref[:, pl.ds(
                pl.multiple_of(c * _LANES, _LANES), _LANES)], h), hit)
    # alive arrives as a (Tr, 1) column: a VPU masked row-sum, exact in
    # f32 like the oracle's matmul
    acc_ref[...] += jnp.sum(alive_ref[...] * hit.astype(jnp.float32),
                            axis=0, keepdims=True)

    @pl.when(rr == nr - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("n", "tile_r", "tile_n", "interpret"))
def token_count(tokens, alive, *, n: int, tile_r: int = 8,
                tile_n: int = 256, interpret: bool = False):
    """tokens: (theta, s_pad) int32 (see codec format), alive: (theta,)
    f32/bool -> counter (n,) int32.  Sentinel tokens (code 0 at the
    past-the-end block) decode to nothing; pad columns past ``n`` stay
    zero because the encoder zero-pads the trailing byte."""
    theta, s_pad = tokens.shape
    tr = min(tile_r, max(theta, 1))
    tn = tile_n
    tp = _pad.pad_to(tokens, 0, tr)  # zero-pad rows: block 0 code 0 -> no bits
    ap = _pad.pad_to(alive.astype(jnp.float32).reshape(-1, 1), 0, tr)
    ncols = -(-n // tn) * tn
    grid = (ncols // tn, pl.cdiv(theta, tr))
    out = pl.pallas_call(
        _token_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, 1), lambda j, r: (r, 0)),
            pl.BlockSpec((tr, s_pad), lambda j, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((1, tn), lambda j, r: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, ncols), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, tn), jnp.float32)],
        interpret=interpret,
    )(ap, tp)
    return out[0, :n]
