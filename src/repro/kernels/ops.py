"""Jit'd dispatch wrappers: Pallas kernel on TPU, ref.py oracle elsewhere.

The backend decides: on a TPU every wrapper runs the compiled Pallas
kernel — there is no path to the oracle there.  Off-TPU the jnp oracle
runs (the CPU test path), and ``interpret=True`` forces the Pallas
kernel through the interpreter (CPU validation — what the kernel tests
use).

`ic_frontier_step` is also the execution step of the engine's ``pallas``
traversal backend (``repro.core.sampler``: ``make_sampler(model,
"pallas")`` / ``IMMConfig(backend="pallas")``): the sampler loop calls
through this dispatch, so a pallas-backed engine runs the fused MXU
kernel on TPU and falls back to the bitwise-equivalent jnp oracle
anywhere else — same math, so off-TPU results match the ``dense``
backend exactly.

Every wrapper records the resolved implementation on the
``kernels.dispatch{kernel=...,impl=pallas|interpret|oracle}`` obs
counter, so benches and CI can *prove* which path ran instead of
inferring it from ``device_kind``.  The recording happens in the host
Python wrapper — i.e. at trace time when the call sits inside ``jit`` /
``shard_map`` — so the counter counts *compilations routed through each
impl*, not executions (a cached jit re-executes without re-dispatching).
That is exactly the question CI asks ("which impl was compiled in?"),
and it keeps the obs package's no-device-code contract intact.
"""
from __future__ import annotations

import jax

from repro import obs
from repro.kernels import ref
from repro.kernels.commit import arena_commit as _commit_pallas
from repro.kernels.coverage_matvec import coverage_matvec as _coverage_pallas
from repro.kernels.coverage_rows import coverage_rows as _rows_pallas
from repro.kernels.coverage_rows import row_view as _row_view_pallas
from repro.kernels.ic_frontier import ic_frontier_step as _frontier_pallas
from repro.kernels.fm_interaction import fm_interaction as _fm_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.packed_count import packed_count as _packed_count_pallas
from repro.kernels.packed_count import token_count as _token_count_pallas
from repro.kernels.segment_or import segment_or as _segment_or_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(interpret: bool = False) -> str:
    """The impl a dispatch routes to, without calling it: ``"interpret"``
    (Pallas through the interpreter), ``"pallas"`` (compiled kernel, the
    only choice on a TPU), or ``"oracle"`` (the jnp reference, off-TPU)."""
    if interpret:
        return "interpret"
    return "pallas" if _on_tpu() else "oracle"


def _dispatch(kernel: str, interpret) -> bool:
    """Resolve the impl, record ``kernels.dispatch``, return whether the
    Pallas entry point (compiled or interpreted) should run."""
    impl = resolve_impl(interpret)
    obs.counter("kernels.dispatch", kernel=kernel, impl=impl).add(1)
    return impl != "oracle"


def coverage_matvec(alive, R, *, interpret=False, **kw):
    if _dispatch("coverage_matvec", interpret):
        return _coverage_pallas(alive, R, interpret=interpret, **kw)
    return ref.coverage_matvec_ref(alive, R)


def row_view(R, valid, *, interpret=False):
    if _dispatch("row_view", interpret):
        return _row_view_pallas(R, valid, interpret=interpret)
    return ref.row_view_ref(R, valid)


def coverage_rows(listed, W, *, interpret=False):
    if _dispatch("coverage_rows", interpret):
        return _rows_pallas(listed, W, interpret=interpret)
    return ref.coverage_rows_ref(listed, W)


def ic_frontier_step(frontier, visited, logq, rand, *, interpret=False,
                     **kw):
    if _dispatch("ic_frontier_step", interpret):
        return _frontier_pallas(frontier, visited, logq, rand,
                                interpret=interpret, **kw)
    return ref.ic_frontier_ref(frontier, visited, logq, rand).astype("uint8")


def segment_or(live, src, *, n, interpret=False, **kw):
    if _dispatch("segment_or", interpret):
        return _segment_or_pallas(live, src, n=n, interpret=interpret, **kw)
    return ref.segment_or_ref(live, src, n)


def arena_commit(rows, *, kind="bitmap", interpret=False, **kw):
    if _dispatch("arena_commit", interpret):
        return _commit_pallas(rows, kind=kind, interpret=interpret, **kw)
    return ref.arena_commit_ref(rows, kind)


def packed_count(packed, alive, *, n, interpret=False, **kw):
    if _dispatch("packed_count", interpret):
        return _packed_count_pallas(packed, alive, n=n,
                                    interpret=interpret, **kw)
    return ref.packed_count_ref(packed, alive, n)


def token_count(tokens, alive, *, n, interpret=False, **kw):
    if _dispatch("token_count", interpret):
        return _token_count_pallas(tokens, alive, n=n,
                                   interpret=interpret, **kw)
    return ref.token_count_ref(tokens, alive, n)


def arena_count(R, alive, *, codec=None, interpret=False):
    """Per-column count ``(n,) f32`` of the ``alive`` rows of an at-rest
    arena (or one tile of it) in the layout ``codec`` names — bitmap
    (``codec`` None or of kind ``"bitmap"``) through `coverage_matvec`,
    packed and token rows through the decode-and-count kernels — read
    tile by tile, never widened or decoded whole.  Counts are exact
    integers in f32."""
    kind = "bitmap" if codec is None else codec.kind
    a = alive.astype("float32")
    if kind == "bitmap":
        return coverage_matvec(a, R, interpret=interpret)
    count = packed_count if kind == "packed" else token_count
    return count(R, a, n=codec.n_cols, interpret=interpret).astype("float32")


def fm_interaction(v, *, interpret=False, **kw):
    if _dispatch("fm_interaction", interpret):
        return _fm_pallas(v, interpret=interpret, **kw)
    return ref.fm_interaction_ref(v)


def flash_attention(q, k, v, *, causal=True, window=0, interpret=False,
                    **kw):
    if _dispatch("flash_attention", interpret):
        return _flash_pallas(q, k, v, causal=causal, window=window,
                             interpret=interpret, **kw)
    return ref.attention_ref(q, k, v, causal=causal, window=window)
