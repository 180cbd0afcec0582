"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


# ------------------------------------------------------ coverage_matvec ----

@pytest.mark.parametrize("theta,n", [(64, 100), (300, 700), (1024, 512),
                                     (257, 1000), (1, 33)])
@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.int8])
def test_coverage_matvec_sweep(theta, n, dtype):
    key = jax.random.PRNGKey(theta * 7 + n)
    R = (jax.random.uniform(key, (theta, n)) < 0.3).astype(dtype)
    alive = jax.random.uniform(jax.random.PRNGKey(1), (theta,)) < 0.7
    got = ops.coverage_matvec(alive, R, interpret=True)
    want = ref.coverage_matvec_ref(alive, R)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("tile_theta,tile_n", [(64, 128), (256, 512),
                                               (128, 256)])
def test_coverage_matvec_tilings(tile_theta, tile_n):
    key = jax.random.PRNGKey(0)
    R = (jax.random.uniform(key, (500, 900)) < 0.2).astype(jnp.uint8)
    alive = jax.random.uniform(jax.random.PRNGKey(1), (500,)) < 0.5
    got = ops.coverage_matvec(alive, R, interpret=True,
                              tile_theta=tile_theta, tile_n=tile_n)
    want = ref.coverage_matvec_ref(alive, R)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ------------------------------------------------ row_view, coverage_rows ----

def _per_vertex(planes, n):
    """(4, C, 128) count planes -> (n,) counts in vertex order."""
    from repro.kernels.coverage_rows import vertex_ids
    v = np.asarray(vertex_ids(planes.shape[1])).ravel()
    out = np.zeros(v.size, np.int64)
    out[v] = np.asarray(planes).ravel()
    assert not out[n:].any()          # vertices past n count 0
    return out[:n]


@pytest.mark.parametrize("theta,n", [(80, 300), (600, 5000), (256, 4096),
                                     (300, 4097), (1, 33)])
def test_row_view_sweep(theta, n):
    rng = np.random.default_rng(theta * 7 + n)
    R = (rng.random((theta, n)) < 0.3).astype(np.uint8)
    valid = rng.random(theta) < 0.7
    W, counts = ops.row_view(jnp.asarray(R), jnp.asarray(valid),
                             interpret=True)
    W_ref, counts_ref = ref.row_view_ref(jnp.asarray(R), jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(W), np.asarray(W_ref))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_ref))
    np.testing.assert_array_equal(_per_vertex(counts, n),
                                  R[valid].sum(axis=0))


@pytest.mark.parametrize("theta,n", [(80, 300), (600, 5000), (1, 40)])
@pytest.mark.parametrize("listed", [0, 1, 7, 255, 256, 511, 600])
def test_coverage_rows_sweep(theta, n, listed):
    """Listed prefixes around the packed sums' 255-row flush."""
    listed = min(listed, theta)
    rng = np.random.default_rng(listed + n)
    R = (rng.random((theta, n)) < 0.5).astype(np.uint8)
    W, _ = ref.row_view_ref(jnp.asarray(R), jnp.ones((theta,), bool))
    rows = rng.permutation(theta)[:listed]
    mask = np.zeros(theta, bool)
    mask[rows] = True
    got = ops.coverage_rows(jnp.asarray(mask), W, interpret=True)
    want = ref.coverage_rows_ref(jnp.asarray(mask), W)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(_per_vertex(got, n),
                                  R[rows].sum(axis=0))


# ------------------------------------------------------------ ic_frontier ----

@pytest.mark.parametrize("B,n", [(16, 64), (64, 200), (128, 513)])
def test_ic_frontier_sweep(B, n):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(B + n), 3)
    frontier = jax.random.uniform(k1, (B, n)) < 0.1
    visited = jnp.logical_or(frontier,
                             jax.random.uniform(k2, (B, n)) < 0.2)
    P = jnp.where(jax.random.uniform(k3, (n, n)) < 0.05,
                  jax.random.uniform(k1, (n, n)), 0.0)
    logq = jnp.maximum(jnp.log1p(-P), -30.0)
    rand = jax.random.uniform(k2, (B, n))
    got = ops.ic_frontier_step(frontier, visited, logq, rand,
                               interpret=True)
    want = ref.ic_frontier_ref(frontier, visited, logq, rand)
    np.testing.assert_array_equal(np.asarray(got).astype(bool),
                                  np.asarray(want))


# --------------------------------------------------------- fm_interaction ----

@pytest.mark.parametrize("B,F,K", [(32, 39, 10), (100, 8, 4), (1025, 16, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fm_interaction_sweep(B, F, K, dtype):
    v = (jax.random.normal(jax.random.PRNGKey(B), (B, F, K)) * 0.3
         ).astype(dtype)
    got = ops.fm_interaction(v, interpret=True)
    want = ref.fm_interaction_ref(v.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_fm_interaction_matches_explicit_pairwise():
    """Sum-square trick == explicit sum_{i<j} <v_i, v_j>."""
    v = jax.random.normal(jax.random.PRNGKey(0), (16, 6, 4))
    got = ops.fm_interaction(v, interpret=True)
    inner = jnp.einsum("bik,bjk->bij", v, v)
    iu = jnp.triu_indices(6, k=1)
    want = inner[:, iu[0], iu[1]].sum(-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# -------------------------------------------------------- flash_attention ----

@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D", [
    (2, 8, 8, 64, 64, 32),       # MHA
    (2, 8, 2, 64, 64, 32),       # GQA 4:1
    (1, 4, 1, 128, 128, 64),     # MQA
    (2, 4, 2, 1, 128, 64),       # decode shape
    (1, 4, 4, 100, 100, 32),     # non-tile-multiple
])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D):
    keys = jax.random.split(jax.random.PRNGKey(Sq + Skv), 3)
    q = jax.random.normal(keys[0], (B, Hq, Sq, D))
    k = jax.random.normal(keys[1], (B, Hkv, Skv, D))
    v = jax.random.normal(keys[2], (B, Hkv, Skv, D))
    got = ops.flash_attention(q, k, v, causal=True, interpret=True,
                              tile_q=32, tile_k=32)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [8, 32])
def test_flash_attention_sliding_window(window):
    keys = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(keys[0], (1, 4, 96, 32))
    k = jax.random.normal(keys[1], (1, 2, 96, 32))
    v = jax.random.normal(keys[2], (1, 2, 96, 32))
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              interpret=True, tile_q=32, tile_k=32)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (1, 4, 64, 32)).astype(dtype)
    k = jax.random.normal(keys[1], (1, 4, 64, 32)).astype(dtype)
    v = jax.random.normal(keys[2], (1, 4, 64, 32)).astype(dtype)
    got = ops.flash_attention(q, k, v, interpret=True)
    want = ref.attention_ref(q, k, v)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


# ----------------------------------------------------------- arena_commit ----
#
# the commit tail of the fused sample->write->count chain: encode (bitmap
# passthrough or MXU bit-pack) + exact int32 column count in one pass.
# Equality is bitwise, not approximate — the engine's fused path commits
# these bytes and counts directly into the arena.

@pytest.mark.parametrize("B,n", [(64, 128), (33, 100), (128, 1000),
                                 (1, 7), (127, 513)])
@pytest.mark.parametrize("kind", ["bitmap", "packed"])
def test_arena_commit_bitwise(B, n, kind):
    key = jax.random.PRNGKey(B * 13 + n)
    rows = (jax.random.uniform(key, (B, n)) < 0.3).astype(jnp.uint8)
    stored, colsum = ops.arena_commit(rows, kind=kind, interpret=True)
    sref, cref = ref.arena_commit_ref(rows, kind=kind)
    np.testing.assert_array_equal(np.asarray(stored), np.asarray(sref))
    np.testing.assert_array_equal(np.asarray(colsum), np.asarray(cref))


@pytest.mark.parametrize("kind", ["bitmap", "packed"])
def test_arena_commit_tilings(kind):
    rows = (jax.random.uniform(jax.random.PRNGKey(3), (200, 300))
            < 0.5).astype(jnp.uint8)
    got_s, got_c = ops.arena_commit(rows, kind=kind, interpret=True,
                                    tile_rows=64, tile_n=128)
    ref_s, ref_c = ref.arena_commit_ref(rows, kind=kind)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(ref_s))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(ref_c))


# ---------------------------------------------------------- segment_or ----
# The sparse sampler's pull reduction: bitwise against the sorted
# segment_max oracle, on skewed segments (long runs crossing many edge
# tiles, empty vertex blocks, a last tile cut short).

def _segments(n, m, seed):
    rng = np.random.default_rng(seed)
    deg = rng.zipf(1.6, size=n).astype(np.float64)
    deg[rng.random(n) < 0.4] = 0             # vertices with no out-edges
    src = np.repeat(np.arange(n), np.floor(deg * m / deg.sum()).astype(int))
    src = np.sort(np.concatenate([src, rng.integers(0, n, m - src.size)]))
    return jnp.asarray(src[:m], jnp.int32)


@pytest.mark.parametrize("n,m,B", [(300, 2000, 128), (1000, 500, 256),
                                   (50, 4000, 128), (7, 9, 128)])
def test_segment_or_bitwise(n, m, B):
    src = _segments(n, m, n + m)
    live = jax.random.uniform(jax.random.PRNGKey(m), (m, B)) < 0.05
    got = ops.segment_or(live, src, n=n, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.segment_or_ref(live, src, n)))


@pytest.mark.parametrize("tile_v,tile_e", [(8, 128), (128, 256), (512, 512)])
def test_segment_or_tilings(tile_v, tile_e):
    n, m = 600, 3000
    src = _segments(n, m, 7)
    live = jax.random.uniform(jax.random.PRNGKey(1), (m, 128)) < 0.01
    got = ops.segment_or(live, src, n=n, interpret=True, tile_v=tile_v,
                         tile_e=tile_e)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.segment_or_ref(live, src, n)))
