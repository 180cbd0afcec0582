"""Greedy max-coverage directly over encoded arenas.

`select_packed` never decodes the bit-packed arena: each greedy round
rebuilds the counter with the decode-and-count kernel
(``kernels/ops.packed_count``) through `repro.core.selection.select_fused`,
so the arena stays 8x smaller at every step.  `select_compressed`
never materializes the decoded arena either: each greedy round rebuilds
the counter with the decode-and-count kernel (``kernels/ops.token_count``
— Pallas on TPU, jnp oracle elsewhere, ``interpret=True`` validates the
kernel on CPU) and tests the winner's membership by token comparison.
Both are bitwise-identical to `select_dense` over the same rows: counts
are integers in f32, so every argmax and tie-break agrees.

Registered layouts: ``{rebuild,decrement}-{packed,compressed}`` (the
sharded layouts reuse ``rebuild-sharded`` with a tile codec — see
`select_dense_sharded`).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.pack.codec import codec_for, token_decode_cols
from repro.core.selection import register_selection, select_fused
from repro.kernels import ops


@partial(jax.jit, static_argnames=("n", "k", "method"))
def select_packed(Rp, valid, n: int, k: int, method: str = "rebuild"):
    """Rp: (theta, ceil(n/8)) uint8 bit-packed rows; valid: (theta,)
    bool.  Returns (seeds (k,) int32, covered_frac () f32,
    gains (k,) int32) — bitwise-equal to ``select_dense`` on the
    unpacked rows."""
    return select_fused(Rp, valid, n, k, method,
                        codec=codec_for("packed", n))


@partial(jax.jit,
         static_argnames=("n", "k", "method", "interpret"))
def select_compressed(T, valid, n: int, k: int, method: str = "rebuild",
                      *, interpret: bool = False):
    """T: (theta, s_pad) int32 token rows (``repro.core.pack.codec``
    format); valid: (theta,) bool.  Greedy selection whose per-round
    counter comes from the decode-and-count kernel — the decoded
    ``(theta, n)`` arena never exists.  Returns (seeds, covered_frac,
    gains) bitwise-equal to ``select_dense`` on the decoded rows."""

    def counter_of(alive):
        return ops.token_count(
            T, alive.astype(jnp.float32), n=n,
            interpret=interpret).astype(jnp.float32)

    def member(v):
        return token_decode_cols(T, v.reshape(1))[:, 0]

    if method == "rebuild":
        def body(i, state):
            alive, seeds, gains = state
            counter = counter_of(alive)
            v = jnp.argmax(counter).astype(jnp.int32)
            covered = member(v) & alive
            gain = covered.sum(dtype=jnp.int32)
            return alive & ~covered, seeds.at[i].set(v), gains.at[i].set(gain)

        alive, seeds, gains = jax.lax.fori_loop(
            0, k, body,
            (valid, jnp.zeros((k,), jnp.int32), jnp.zeros((k,), jnp.int32)))
    elif method == "decrement":
        def body(i, state):
            alive, counter, seeds, gains = state
            v = jnp.argmax(counter).astype(jnp.int32)
            covered = member(v) & alive
            gain = covered.sum(dtype=jnp.int32)
            counter = counter - counter_of(covered)
            return (alive & ~covered, counter,
                    seeds.at[i].set(v), gains.at[i].set(gain))

        alive, _, seeds, gains = jax.lax.fori_loop(
            0, k, body,
            (valid, counter_of(valid), jnp.zeros((k,), jnp.int32),
             jnp.zeros((k,), jnp.int32)))
    else:
        raise ValueError(f"unknown method {method}")

    n_valid = jnp.maximum(valid.sum(dtype=jnp.float32), 1.0)
    return seeds, gains.sum(dtype=jnp.float32) / n_valid, gains


def _packed_strategy(method):
    def run(view, k, **_):
        return select_packed(view.R, view.valid, view.n, k, method)
    return run


def _compressed_strategy(method):
    def run(view, k, *, pallas_interpret=False, **_):
        return select_compressed(view.R, view.valid, view.n, k, method,
                                 interpret=bool(pallas_interpret))
    return run


for _m in ("rebuild", "decrement"):
    register_selection(f"{_m}-packed", _packed_strategy(_m))
    register_selection(f"{_m}-compressed", _compressed_strategy(_m))
