"""Property tests (hypothesis) for the shared sparse primitives — the layer
the IMM counters, GNN aggregation and recsys lookups all reduce to."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")  # property tests need it; skip on clean machines
from hypothesis import given, settings, strategies as st

from repro.sparse import (
    segment_sum, segment_max, segment_mean, segment_softmax,
    bincount_weighted, one_hot_matmul_count, embedding_bag,
)


settings.register_profile("ci", deadline=None, max_examples=25)
settings.load_profile("ci")


@st.composite
def segments(draw):
    n = draw(st.integers(1, 50))
    buckets = draw(st.integers(1, 10))
    ids = draw(st.lists(st.integers(0, buckets), min_size=n, max_size=n))
    data = draw(st.lists(
        st.floats(-10, 10, allow_nan=False, width=32),
        min_size=n, max_size=n))
    return (np.array(ids, np.int32), np.array(data, np.float32), buckets)


@given(segments())
def test_segment_sum_matches_numpy(sd):
    ids, data, buckets = sd
    got = segment_sum(jnp.asarray(data), jnp.asarray(ids), buckets)
    want = np.zeros(buckets, np.float32)
    for i, d in zip(ids, data):
        if i < buckets:      # sentinel ids drop
            want[i] += d
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@given(segments())
def test_bincount_weighted_equals_one_hot_matmul(sd):
    ids, data, buckets = sd
    a = bincount_weighted(jnp.asarray(ids), jnp.asarray(data), buckets)
    b = one_hot_matmul_count(jnp.asarray(ids), jnp.asarray(data), buckets)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


@given(segments())
def test_segment_mean_bounded_by_extremes(sd):
    ids, data, buckets = sd
    mean = np.asarray(segment_mean(jnp.asarray(data), jnp.asarray(ids),
                                   buckets))
    for b in range(buckets):
        vals = data[ids == b]
        if len(vals):
            assert vals.min() - 1e-4 <= mean[b] <= vals.max() + 1e-4


@given(segments())
def test_segment_softmax_normalizes(sd):
    ids, data, buckets = sd
    sm = segment_softmax(jnp.asarray(data), jnp.asarray(ids), buckets)
    sums = np.asarray(segment_sum(sm, jnp.asarray(ids), buckets))
    for b in range(buckets):
        if (ids == b).any():
            assert sums[b] == jnp.asarray(1.0, jnp.float32) or \
                abs(sums[b] - 1.0) < 1e-4


def test_segment_max_with_neg_inf_padding():
    data = jnp.array([-jnp.inf, 3.0, -jnp.inf, 1.0])
    ids = jnp.array([0, 0, 1, 1])
    out = segment_max(data, ids, 3)
    assert float(out[0]) == 3.0 and float(out[1]) == 1.0


# ---------------------------------------------------------- embedding bag ----

@given(st.integers(1, 8), st.integers(1, 6), st.integers(2, 30),
       st.integers(1, 5))
def test_embedding_bag_fixed_len_matches_loop(bags, length, vocab, dim):
    key = jax.random.PRNGKey(bags * 7 + length)
    table = jax.random.normal(key, (vocab, dim))
    idx = jax.random.randint(jax.random.PRNGKey(1), (bags, length), 0, vocab)
    got = embedding_bag(table, idx, mode="sum")
    want = np.stack([np.asarray(table)[np.asarray(idx[b])].sum(0)
                     for b in range(bags)])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_embedding_bag_offsets_and_padding():
    table = jnp.arange(12.0).reshape(6, 2)
    indices = jnp.array([0, 1, 2, 5, 6], jnp.int32)   # 6 == vocab -> pad
    offsets = jnp.array([0, 2, 4], jnp.int32)
    out = embedding_bag(table, indices, offsets, mode="sum")
    np.testing.assert_allclose(
        np.asarray(out),
        [[2.0, 4.0], [14.0, 16.0], [0.0, 0.0]])


def test_embedding_bag_modes():
    table = jnp.asarray(np.random.default_rng(0).normal(size=(10, 3)),
                        jnp.float32)
    idx = jnp.array([[1, 2, 3], [4, 4, 4]], jnp.int32)
    s = embedding_bag(table, idx, mode="sum")
    m = embedding_bag(table, idx, mode="mean")
    mx = embedding_bag(table, idx, mode="max")
    np.testing.assert_allclose(np.asarray(m), np.asarray(s) / 3, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(mx)[1], np.asarray(table)[4], rtol=1e-5)


def test_sharded_embedding_lookup_single_device():
    """shard_map row-sharded lookup == plain take on a 1-device mesh."""
    from repro.compat import shard_map
    from repro.launch.mesh import make_mesh
    from repro.sparse import sharded_embedding_lookup
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((1,), ("model",))
    table = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    ids = jnp.array([[0, 3], [15, 7]], jnp.int32)
    fn = shard_map(
        lambda t, i: sharded_embedding_lookup(
            t, i, axis_name="model", shard_rows=16),
        mesh=mesh, in_specs=(P("model", None), P()), out_specs=P())
    got = fn(table, ids)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.take(table, ids, axis=0)),
                               rtol=1e-6)
