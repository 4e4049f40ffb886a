"""Betweenness Centrality vs networkx (exact Brandes oracle) and vs the
plain float64 reference ``bc_reference``."""
import networkx as nx
import numpy as np
import pytest

from repro.algorithms.betweenness import (RMATParams, bc_reference,
                                          bc_single_node, bc_spec,
                                          betweenness_centrality,
                                          rmat_graph)
from repro.core import ElasticExecutor, LocalExecutor, make_pool, run_irregular


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
