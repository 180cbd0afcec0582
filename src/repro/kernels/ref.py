"""Pure-jnp oracles for every Pallas kernel (the CPU/dry-run execution path).

Each function is the semantic ground truth that the corresponding kernel in
this package must match (tests/test_kernels.py sweeps shapes/dtypes in
interpret mode against these).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def coverage_matvec_ref(alive, R):
    """alive: (theta,) f32/bool, R: (theta, n) uint8 -> counter (n,) f32.

    The EfficientIMM counter rebuild (paper C5): counter[v] = #survivor sets
    containing v.
    """
    return alive.astype(jnp.float32) @ R.astype(jnp.float32)


def row_view_ref(R, valid):
    """R: (theta, n) uint8 0/1, valid: (theta,) -> (W (theta, C, 128)
    int32, counts (32, C, 128) int32): the set-major bit-packed copy of
    the arena (bit ``k`` of word ``(c, l)`` of a row is vertex
    ``vertex_ids(C)[k, c, l]``; zero past ``n``) and the count of the
    ``valid`` rows, vertex ``vertex_ids(C)[k, c, l]`` at ``[k, c, l]``."""
    from repro.kernels.coverage_rows import vertex_ids, words_per_row
    theta, n = R.shape
    c = words_per_row(n)
    ids = vertex_ids(c)
    pad = c * 4096 - n
    bits = jnp.pad(R, ((0, 0), (0, pad)))[:, ids].astype(jnp.uint32)
    W = (bits << jnp.arange(32, dtype=jnp.uint32)[:, None, None]).sum(
        axis=1, dtype=jnp.uint32)
    counts = coverage_matvec_ref(valid, R).astype(jnp.int32)
    return (jax.lax.bitcast_convert_type(W, jnp.int32),
            jnp.pad(counts, (0, pad))[ids])


def coverage_rows_ref(listed, W):
    """listed: (theta,) bool, W: a `row_view_ref` arena -> (32, C, 128)
    int32 count planes of the listed rows."""
    words = jax.lax.bitcast_convert_type(W, jnp.uint32)[..., None]
    bits = (words >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return jnp.einsum("r,rclk->kcl", listed.astype(jnp.int32),
                      bits.astype(jnp.int32))


def ic_frontier_ref(frontier, visited, logq, rand):
    """One probabilistic-BFS step in the log-semiring formulation.

    frontier/visited: (B, n) bool; logq: (n, n) f32 (log(1-p), reverse
    orientation); rand: (B, n) uniform draws.
    Returns new activations (B, n) bool.
    """
    acc = frontier.astype(jnp.float32) @ logq
    p_act = -jnp.expm1(acc)
    return jnp.logical_and(rand < p_act, ~visited)


def segment_or_ref(live, src, n):
    """live: (m, B) 0/1 rows, src: (m,) int32 sorted row owners ->
    (n, B) bool, the OR of each owner's rows (False where it has none)."""
    return jax.ops.segment_max(live.astype(jnp.int8), src, num_segments=n,
                               indices_are_sorted=True) > 0


def fm_interaction_ref(v):
    """FM 2-way interaction via the O(nk) sum-square trick (Rendle ICDM'10).

    v: (B, F, K) field embeddings (already multiplied by feature values).
    Returns (B,) f32: sum_k 0.5 * ((sum_f v)^2 - sum_f v^2).
    """
    s = v.sum(axis=1)
    s2 = (v * v).sum(axis=1)
    return (0.5 * (s * s - s2)).sum(axis=-1)


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  scale: float | None = None):
    """Grouped-query attention oracle.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D); Hq % Hkv == 0.
    window > 0 adds sliding-window masking (attend to keys in
    (pos - window, pos]).  Query positions are right-aligned to the keys
    (q position i corresponds to absolute position Skv - Sq + i), which
    covers both prefill (Sq == Skv) and decode (Sq == 1).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(jnp.float32)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq) + (Skv - Sq)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window and window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def arena_commit_ref(rows, kind: str = "bitmap"):
    """rows: (B, n) uint8/bool 0/1 -> (stored, colsum (n,) int32).

    The fused encode-and-count oracle: ``stored`` is the at-rest block
    (identity for ``"bitmap"``, LSB-first `pack_bits` for ``"packed"``)
    and ``colsum`` is the batch's per-vertex counter contribution — the
    two quantities the store write path needs, in one definition.
    """
    rows = rows.astype(jnp.uint8)
    colsum = rows.sum(axis=0, dtype=jnp.int32)
    if kind == "bitmap":
        return rows, colsum
    if kind != "packed":
        raise ValueError(f"arena_commit kind must be bitmap|packed, "
                         f"got {kind!r}")
    from repro.core.pack.codec import pack_bits
    return pack_bits(rows), colsum


def packed_count_ref(packed, alive, n: int):
    """packed: (theta, ceil(n/8)) uint8 bit-packed rows (LSB-first),
    alive: (theta,) f32/bool -> counter (n,) int32.

    The decode-and-count oracle for bit-packed arenas: unpack to 0/1
    bits, then the exact f32 masked matmul (`coverage_matvec_ref`).
    """
    from repro.core.pack.codec import unpack_bits
    bits = unpack_bits(packed, int(n))
    return (alive.astype(jnp.float32)
            @ bits.astype(jnp.float32)).astype(jnp.int32)


def token_count_ref(tokens, alive, n: int):
    """tokens: (theta, s_pad) int32 literal/run token rows (see
    ``repro.core.pack.codec``), alive: (theta,) -> counter (n,) int32."""
    from repro.core.pack.codec import token_decode
    bits = token_decode(tokens, int(n))
    return (alive.astype(jnp.float32)
            @ bits.astype(jnp.float32)).astype(jnp.int32)
