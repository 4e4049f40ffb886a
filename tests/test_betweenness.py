"""Betweenness Centrality vs networkx (exact Brandes oracle) and vs the
plain float64 reference ``bc_reference``."""
import networkx as nx
import numpy as np
import pytest

from repro.algorithms.betweenness import (RMATParams, bc_reference,
                                          bc_single_node, bc_spec,
                                          betweenness_centrality,
                                          rmat_adjacency, rmat_edges,
                                          rmat_graph)
from repro.core import ElasticExecutor, LocalExecutor, make_pool, run_irregular
from repro.kernels.dispatch import to_device, to_host


def _nx_bc(adj):
    g = nx.from_numpy_array(adj, create_using=nx.DiGraph)
    d = nx.betweenness_centrality(g, normalized=False)
    return np.array([d[i] for i in range(adj.shape[0])])


@pytest.mark.parametrize("seed", [2, 7])
@pytest.mark.parametrize("scale", [5, 6])
def test_matches_networkx(scale, seed):
    adj = rmat_graph(RMATParams(scale=scale, seed=seed))
    ours = bc_single_node(adj, n_tasks=3)
    ref = _nx_bc(adj)
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-3)


def test_partition_invariance():
    """Static partitioning (paper: T tasks) must not change the result."""
    adj = rmat_graph(RMATParams(scale=6, seed=2))
    a = bc_single_node(adj, n_tasks=1)
    b = bc_single_node(adj, n_tasks=7)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


def test_executor_regenerated_graph_matches():
    """Paper Listing 4 line 44: each function regenerates the graph."""
    p = RMATParams(scale=6, seed=2)
    adj = rmat_graph(p)
    expected = bc_single_node(adj, n_tasks=1)
    with LocalExecutor(2, invoke_overhead=0.0) as ex:
        res = betweenness_centrality(ex, p, n_tasks=8,
                                     regenerate_graph=True)
    np.testing.assert_allclose(res.betweenness, expected, rtol=1e-4,
                               atol=1e-3)
    assert res.tasks == 8


def test_rmat_properties():
    p = RMATParams(scale=7, seed=2)
    adj = rmat_graph(p)
    n = p.n_vertices
    assert adj.shape == (n, n)
    assert float(np.trace(adj)) == 0.0           # no self loops
    assert set(np.unique(adj)).issubset({0.0, 1.0})
    # R-MAT a=0.55 skew: some vertices have much higher degree
    deg = adj.sum(1)
    assert deg.max() >= 4 * max(deg.mean(), 1e-9)


def test_rmat_deterministic():
    a = rmat_graph(RMATParams(scale=6, seed=2))
    b = rmat_graph(RMATParams(scale=6, seed=2))
    assert np.array_equal(a, b)


def _looped_rmat_graph(p):
    """The R-MAT generator as first written: one ``rand(m)`` per bit
    level in a Python loop, self-loops dropped before the permutation,
    a host scatter.  Kept frozen as the oracle of ``rmat_edges``."""
    rng = np.random.RandomState(p.seed)
    n = p.n_vertices
    m = p.edge_factor * n
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for _ in range(p.scale):
        r = rng.rand(m)
        q_b = (r >= p.a) & (r < p.a + p.b)
        q_c = (r >= p.a + p.b) & (r < p.a + p.b + p.c)
        q_d = r >= p.a + p.b + p.c
        src = 2 * src + (q_c | q_d)
        dst = 2 * dst + (q_b | q_d)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    adj = np.zeros((n, n), np.float32)
    adj[src, dst] = 1.0
    return adj


@pytest.mark.parametrize("seed", [0, 1, 2, 7, 123])
@pytest.mark.parametrize("scale", [4, 6, 8, 10])
def test_edge_draws_rebuild_the_looped_graph(scale, seed):
    """``rmat_edges`` draws the looped generator's stream: its host
    scatter (``rmat_graph``) and its device scatter
    (``rmat_adjacency``) both give that graph bit for bit."""
    p = RMATParams(scale=scale, seed=seed)
    expected = _looped_rmat_graph(p)
    src, dst = rmat_edges(p)
    assert src.dtype == dst.dtype == np.int32
    assert src.shape == dst.shape == (p.edge_factor * p.n_vertices,)
    np.testing.assert_array_equal(rmat_graph(p), expected)
    built = rmat_adjacency(to_device(np.stack([src, dst])), p.n_vertices)
    np.testing.assert_array_equal(to_host(built), expected)


@pytest.mark.parametrize("batching", [False, True],
                         ids=["execute", "execute_batch"])
def test_regenerated_job_equals_shipped_job(batching):
    """A task that scatters its own edge draws on the device sweeps the
    graph a shipped adjacency holds: the same sums, bit for bit."""
    p = RMATParams(scale=7, seed=2)
    out = {}
    with make_pool("local", max_concurrency=4) as pool:
        for regenerate in (True, False):
            out[regenerate] = run_irregular(
                pool, bc_spec(p, n_tasks=8, regenerate_graph=regenerate),
                batching=batching).output
    np.testing.assert_array_equal(out[True], out[False])


def test_reference_matches_networkx():
    adj = rmat_graph(RMATParams(scale=6, seed=2))
    np.testing.assert_allclose(bc_reference(adj), _nx_bc(adj), rtol=1e-12,
                               atol=1e-9)


@pytest.mark.parametrize("regenerate", [True, False],
                         ids=["regenerated", "shipped"])
@pytest.mark.parametrize("seed", [2, 3, 11])
@pytest.mark.parametrize("scale", [6, 7, 8])
def test_pool_matches_reference(scale, seed, regenerate):
    """The pool's float32 sums against float64 Brandes: the error the
    benchmark's comparison bounds by 1e-5."""
    p = RMATParams(scale=scale, seed=seed)
    with make_pool("local", max_concurrency=4) as pool:
        res = run_irregular(pool, bc_spec(p, n_tasks=8,
                                          regenerate_graph=regenerate))
    ref = bc_reference(rmat_graph(p))
    assert res.tasks == 8
    assert np.max(np.abs(res.output - ref) / np.maximum(ref, 1.0)) <= 1e-5
