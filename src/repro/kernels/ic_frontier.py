"""Pallas TPU kernel: one IC probabilistic-BFS frontier expansion step.

The beyond-paper MXU formulation (DESIGN §2): the probability that vertex u
is activated by the current frontier is 1 - prod_{v in F}(1 - p), so one BFS
step is ``new = (rand < 1 - exp(frontier @ logq)) & ~visited`` — a matmul in
the log-semiring followed by Bernoulli sampling and the visited-bitmap mask
(the paper's hottest data structure, Alg. 3 line 8).

Grid: (B/Tb, n/Tn, n/Tk) with the contraction axis minor; the log-survival
accumulates in VMEM scratch and is written once on the last k tile.  The
sampling epilogue (``-expm1``, compare, mask) runs in the jnp wrapper:
Mosaic has no ``expm1`` lowering, and the epilogue must stay bitwise the
oracle's (`repro.kernels.ref.ic_frontier_ref`) so the ``pallas`` backend
draws the same sets as ``dense``.  Block shapes on v5e: frontier
(Tb, Tk) uint8, logq (Tk, Tn) f32, out (Tb, Tn) f32 with Tb = 128 and
Tn = Tk = 512; ``tests/test_tpu_compile.py`` compiles it at n = 4096.

On a 2D (theta x vertex) mesh this kernel runs inside the dense loop's
double-buffered frontier dispatch (``core/sampler.py::_dense_loop`` with
``overlap=True``): the loop state carries the vertex-axis all-gathered
frontier, so the collective producing step t+1's ``frontier`` operand is
issued while this kernel computes step t — the all-gather hides behind
the MXU matmul instead of serializing with it.  The kernel itself is
oblivious: it always sees a full-width ``(B, n)`` frontier operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _pad


def _kernel(front_ref, logq_ref, out_ref, acc_ref):
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    f = front_ref[...].astype(jnp.int32).astype(jnp.float32)    # (Tb, Tk)
    acc_ref[...] += jnp.dot(f, logq_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("tile_b", "tile_n", "tile_k", "interpret"))
def ic_frontier_step(frontier, visited, logq, rand, *, tile_b: int = 128,
                     tile_n: int = 512, tile_k: int = 512,
                     interpret: bool = False):
    """frontier/visited: (B, n) uint8/bool; logq: (n, n) f32; rand: (B, n).

    Returns new activations (B, n) uint8.
    """
    B, n = frontier.shape
    tb, tn, tk = min(tile_b, B), min(tile_n, n), min(tile_k, n)
    # zero padding is neutral for the log-survival sum: a padded frontier
    # column contributes nothing, padded logq columns are sliced off
    fp = _pad.pad_to(_pad.pad_to(frontier.astype(jnp.uint8), 0, tb), 1, tk)
    lp = _pad.pad_to(_pad.pad_to(logq, 0, tk), 1, tn)
    grid = (pl.cdiv(B, tb), pl.cdiv(n, tn), pl.cdiv(n, tk))
    acc = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, tk), lambda b, i, k: (b, k)),
            pl.BlockSpec((tk, tn), lambda b, i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((tb, tn), lambda b, i, k: (b, i)),
        out_shape=jax.ShapeDtypeStruct((fp.shape[0], lp.shape[1]),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((tb, tn), jnp.float32)],
        interpret=interpret,
    )(fp, lp)[:B, :n]
    p_act = -jnp.expm1(acc)                     # 1 - exp(acc)
    new = (rand < p_act) & (visited.astype(jnp.uint8) == 0)
    return new.astype(jnp.uint8)
