"""Plain references the benchmark compares the program with.

Nothing here imports the program.  Each reference works from what the
benchmark made itself (`bench.data`) and from JAX's own random
functions, which give the engine's coins:

* the key chain: ``PRNGKey(seed)``, then ``(key, sub) = split(key)``
  once per batch of RRR sets, in the order the engine samples them;
* inside a batch: ``(kroot, kstep) = split(sub)``, the roots
  ``randint(kroot, (B,), 0, n)``, then per step of the reverse search
  ``(kstep, s) = split(kstep)`` and the step's draws from ``s``:
  ``uniform(s, (B, m))`` coins over the CSC edge order for IC,
  ``uniform(s, (B,))`` for LT.

Under JAX's partitionable threefry (the default) the coin of set ``b``
at CSC edge ``e`` is a function of the step key and the counter
``b * m + e`` alone, so a sampler that draws only the coins it reads,
with the same counters, gives the same rows.

`ic_rows` and `lt_rows` are reverse-reachable set sampling (RIS) row by
row on those coins, on the host.  `greedy` is greedy max coverage with
exact integer counts, rebuilt from the uncovered rows for each pick,
first index on ties; `hit_counts` counts the rows
a seed set meets.  ``control=True`` computes the same thing one
precision lower (bfloat16 draws, counts or answers): the comparison has
to tell that apart from the program.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from bench.data import Csc


def batch_keys(seed: int, indices) -> dict:
    """``{i: sub_i}`` for the engine's batch keys at ``indices`` (0 is
    the first batch the engine ever samples)."""
    want = set(int(i) for i in indices)
    key = jax.random.PRNGKey(int(seed))
    out = {}
    for i in range(max(want) + 1):
        key, sub = jax.random.split(key)
        if i in want:
            out[i] = sub
    return out


def _roots(sub, batch: int, n: int):
    kroot, kstep = jax.random.split(sub)
    return np.asarray(jax.random.randint(kroot, (batch,), 0, n)), kstep


def _bucket(x: int) -> int:
    b = 1024
    while b < x:
        b <<= 1
    return b


@partial(jax.jit, static_argnames=("batch", "m", "dtype"))
def _coins_at(s, rows, edges, *, batch: int, m: int, dtype):
    return jax.random.uniform(s, (batch, m), dtype=dtype)[rows, edges]


def ic_rows(sub, rows, g: Csc, batch: int, *, control=False) -> list:
    """IC RRR sets of batch ``sub`` at positions ``rows``: sorted
    vertex arrays.  An in-edge ``u -> v`` is tried once, when ``v``
    joins the frontier, with that step's coin, and fires when the coin
    is below its probability."""
    dtype = jnp.bfloat16 if control else jnp.float32
    prob = g.prob.astype(np.float32)
    if control:
        prob = np.asarray(jnp.asarray(prob, jnp.bfloat16).astype(jnp.float32))
    roots, kstep = _roots(sub, batch, g.n)
    rows = [int(r) for r in rows]
    seen = {r: np.zeros(g.n, bool) for r in rows}
    front = {}
    for r in rows:
        seen[r][roots[r]] = True
        front[r] = np.array([roots[r]], np.int64)
    while any(f.size for f in front.values()):
        kstep, s = jax.random.split(kstep)
        ask_r, ask_e = [], []
        for r in rows:
            e = g.in_edges(front[r])
            e = e[~seen[r][g.src[e]]]
            ask_r.append(np.full(e.size, r, np.int32))
            ask_e.append(e.astype(np.int32))
        n_ask = sum(a.size for a in ask_e)
        coins = np.zeros(0, np.float32)
        if n_ask:
            pad = _bucket(n_ask) - n_ask
            rr = np.concatenate(ask_r + [np.zeros(pad, np.int32)])
            ee = np.concatenate(ask_e + [np.zeros(pad, np.int32)])
            coins = np.asarray(_coins_at(s, rr, ee, batch=batch, m=g.m,
                                         dtype=dtype)).astype(np.float32)
        at = 0
        for r, e in zip(rows, ask_e):
            fire = coins[at:at + e.size] < prob[e]
            at += e.size
            new = np.unique(g.src[e[fire]])
            new = new[~seen[r][new]]
            seen[r][new] = True
            front[r] = new
    return [np.flatnonzero(seen[r]) for r in rows]


def lt_rows(sub, rows, g: Csc, batch: int, *, control=False) -> list:
    """LT RRR sets (the reverse random walk) of batch ``sub`` at
    positions ``rows``.  At vertex ``v`` one uniform ``r`` stops the
    walk when ``r >= total(v)``, else picks the first in-edge whose
    cumulative weight reaches ``r``; a walk that picks a vertex it has
    visited stops."""
    dtype = jnp.bfloat16 if control else jnp.float32
    cum, total = g.cum, g.total
    if control:
        cum = np.asarray(jnp.asarray(cum, jnp.bfloat16).astype(jnp.float32))
        total = np.asarray(
            jnp.asarray(total, jnp.bfloat16).astype(jnp.float32))
    roots, kstep = _roots(sub, batch, g.n)
    walk = {int(r): [int(roots[r])] for r in rows}
    live = set(walk)
    while live:
        kstep, s = jax.random.split(kstep)
        draw = np.asarray(jax.random.uniform(s, (batch,), dtype=dtype)
                          ).astype(np.float32)
        for r in sorted(live):
            cur = walk[r][-1]
            if not draw[r] < total[cur]:
                live.discard(r)
                continue
            lo, hi = g.off[cur], g.off[cur + 1]
            j = lo + int(np.searchsorted(cum[lo:hi], draw[r], side="left"))
            nxt = int(g.src[j]) if j < hi else None
            if nxt is None or nxt in walk[r]:
                live.discard(r)
                continue
            walk[r].append(nxt)
    return [np.unique(np.asarray(walk[int(r)])) for r in rows]


@partial(jax.jit, static_argnames=("dtype",))
def _colsum(R, mask, dtype=jnp.int32):
    """Column sums of the rows of ``R`` under ``mask``, one block of
    rows at a time (no widened copy of the whole arena): exact in int32,
    or each block's sum added up in ``dtype``."""
    blk = 512
    rows = R.shape[0]
    nblk = -(-rows // blk)
    Rp = jnp.pad(R, ((0, nblk * blk - rows), (0, 0)))
    mp = jnp.pad(mask, (0, nblk * blk - rows))

    def body(i, acc):
        r = jax.lax.dynamic_slice_in_dim(Rp, i * blk, blk)
        w = jax.lax.dynamic_slice_in_dim(mp, i * blk, blk)
        part = jnp.where(w[:, None], r.astype(jnp.int32), 0).sum(0)
        return (acc + part.astype(dtype)).astype(dtype)

    return jax.lax.fori_loop(0, nblk, body, jnp.zeros((R.shape[1],), dtype))


def colsum(R, mask, dtype=jnp.int32):
    return _colsum(R, jnp.asarray(mask), dtype=dtype)


@jax.jit
def rowsum(R):
    """Members per row of ``R``, in int32."""
    return R.sum(axis=1, dtype=jnp.int32)


def greedy(R, count: int, k: int, *, control=False):
    """Greedy max coverage over the first ``count`` rows of ``R`` (a
    device array).  Returns (seeds, gains) as int64 numpy arrays."""
    dtype = jnp.bfloat16 if control else jnp.int32
    alive = jnp.arange(R.shape[0]) < count
    seeds, gains = [], []
    for _ in range(k):
        v = int(jnp.argmax(colsum(R, alive, dtype)))
        hit = alive & (R[:, v] > 0)
        gains.append(int(hit.sum()))
        seeds.append(v)
        alive = alive & ~hit
    return np.asarray(seeds), np.asarray(gains)


def hit_counts(R, count: int, sets, *, control=False) -> np.ndarray:
    """For each seed set, the number of the first ``count`` rows of
    ``R`` that hold one of its vertices; with ``control`` the count as a
    bfloat16 share of ``count``, scaled back."""
    verts = np.unique(np.concatenate([np.asarray(s) for s in sets]))
    where = {int(v): i for i, v in enumerate(verts)}
    cols = np.concatenate(
        [np.asarray(R[:count, jnp.asarray(verts[i:i + 4096])])
         for i in range(0, verts.size, 4096)], axis=1)
    out = np.empty(len(sets), np.int64)
    for q, s in enumerate(sets):
        idx = [where[int(v)] for v in s]
        out[q] = np.count_nonzero(cols[:, idx].any(axis=1))
    if control:
        share = np.asarray(jnp.asarray(out / count, jnp.bfloat16)
                           .astype(jnp.float32), np.float64)
        out = np.rint(share * count).astype(np.int64)
    return out
