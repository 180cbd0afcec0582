"""Greedy max-coverage directly over encoded arenas.

`select_packed` (bit-packed rows) and `select_compressed` (token rows)
run the one greedy loop of `repro.core.selection` with the codec
provider: every count goes through the decode-and-count kernels
(``kernels/ops.packed_count`` / ``token_count`` — Pallas on TPU, jnp
oracle elsewhere, ``interpret=True`` validates the kernels on CPU) and
the winner's membership is a one-column decode, so the decoded
``(theta, n)`` arena never exists.  Both update rules, ``rebuild`` and
``decrement``, are served, and both are bitwise-identical to
`select_dense` over the same rows: counts are integers in f32, so every
argmax and tie-break agrees.

Registered layouts: ``{rebuild,decrement}-{packed,compressed}`` (the
sharded layouts reuse ``<method>-sharded`` with a tile codec — see
`select_dense_sharded`).
"""
from __future__ import annotations

from functools import partial

import jax

from repro.core.pack.codec import codec_for
from repro.core.selection import _codec_tally, _full_pass, register_selection


@partial(jax.jit, static_argnames=("n", "k", "method", "interpret"))
def select_packed(Rp, valid, n: int, k: int, method: str = "rebuild",
                  *, interpret: bool = False):
    """Rp: (theta, ceil(n/8)) uint8 bit-packed rows; valid: (theta,)
    bool.  Returns (seeds (k,) int32, covered_frac () f32,
    gains (k,) int32) — bitwise-equal to ``select_dense`` on the
    unpacked rows."""
    return _full_pass(k, valid, method, *_codec_tally(
        Rp, codec_for("packed", n), interpret))[:3]


@partial(jax.jit, static_argnames=("n", "k", "method", "interpret"))
def select_compressed(T, valid, n: int, k: int, method: str = "rebuild",
                      *, interpret: bool = False):
    """T: (theta, s_pad) int32 token rows (``repro.core.pack.codec``
    format); valid: (theta,) bool.  Returns (seeds, covered_frac,
    gains) bitwise-equal to ``select_dense`` on the decoded rows."""
    return _full_pass(k, valid, method, *_codec_tally(
        T, codec_for("compressed", n, T.shape[1]), interpret))[:3]


def _codec_strategy(select, method):
    def run(view, k, *, pallas_interpret=False, **_):
        return select(view.R, view.valid, view.n, k, method,
                      interpret=bool(pallas_interpret))
    return run


for _m in ("rebuild", "decrement"):
    register_selection(f"{_m}-packed", _codec_strategy(select_packed, _m))
    register_selection(f"{_m}-compressed",
                       _codec_strategy(select_compressed, _m))
