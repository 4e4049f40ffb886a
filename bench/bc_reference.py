"""Plain reference for the benchmark's betweenness-centrality jobs,
independent of the program.

Nothing here imports the system under test.  The graph is the R-MAT
digraph of the SSCA#2 generator as the configuration states it
(Chakrabarti et al.: one quadrant draw per bit of the vertex ids from
``numpy.random.RandomState(seed)``, duplicate samples one edge,
self-loops dropped, vertex ids permuted); betweenness is Brandes'
algorithm in float64 over a sparse adjacency, level-synchronous over
blocks of sources, the blocks spread over host threads.

As the program does, the reference reads the graph as directed and
unweighted, takes every vertex as a source, and leaves a source out of
its own dependency.

``precision`` names what the operands of each product are rounded to
before it: ``float64`` leaves them as they are; ``bfloat16`` rounds
them as a TPU product at default precision does (one bfloat16 pass,
float32 sums), which is the control the comparison must reject.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import ml_dtypes
import numpy as np
import scipy.sparse as sp

#: sources per level-synchronous pass
BLOCK = 128


def rmat_edges(scale: int, edge_factor: int, a: float, b: float, c: float,
               seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(source, target) ids of the R-MAT digraph's distinct edges.

    ``edge_factor * 2**scale`` samples; at each of ``scale`` bits one
    uniform draw picks the quadrant (a | b | c | d = 1 - a - b - c),
    which sets that bit of the target (b, d) and of the source (c, d).
    """
    rng = np.random.RandomState(seed)
    n = 1 << scale
    m = edge_factor * n
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for _ in range(scale):
        r = rng.rand(m)
        src = 2 * src + (r >= a + b)
        dst = 2 * dst + (((r >= a) & (r < a + b)) | (r >= a + b + c))
    keep = src != dst
    perm = rng.permutation(n)
    src, dst = perm[src[keep]], perm[dst[keep]]
    pairs = np.unique(src * n + dst)
    return pairs // n, pairs % n


def _rounded(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return x
    if precision == "bfloat16":
        return x.astype(np.float32).astype(ml_dtypes.bfloat16).astype(
            np.float64)
    raise ValueError(f"no precision {precision!r}")


def _block(fwd: sp.csr_matrix, bwd: sp.csr_matrix, sources: np.ndarray,
           precision: str) -> np.ndarray:
    """Sum over ``sources`` of their dependency on every vertex.

    Arrays are [N, S], a column per source: ``fwd`` (the transposed
    adjacency) takes a frontier one level out, ``bwd`` (the adjacency)
    brings dependencies one level back."""
    n, s = fwd.shape[0], sources.size
    cols = np.arange(s)
    dist = np.full((n, s), -1, np.int64)
    sigma = np.zeros((n, s))
    dist[sources, cols] = 0
    sigma[sources, cols] = 1.0
    depth = 0
    while True:
        reach = fwd @ _rounded(np.where(dist == depth, sigma, 0.0),
                               precision)
        new = (dist < 0) & (reach > 0)
        if not new.any():
            break
        depth += 1
        dist[new] = depth
        sigma[new] = reach[new]
    delta = np.zeros((n, s))
    safe_sigma = np.where(sigma > 0, sigma, 1.0)
    for lvl in range(depth, 0, -1):
        coeff = np.where(dist == lvl, (1.0 + delta) / safe_sigma, 0.0)
        back = bwd @ _rounded(coeff, precision)
        delta += np.where(dist == lvl - 1, sigma * back, 0.0)
    delta[sources, cols] = 0.0
    return delta.sum(axis=1)


def betweenness(src: np.ndarray, dst: np.ndarray, n: int,
                precision: str = "float64",
                threads: int = os.cpu_count() or 1) -> np.ndarray:
    """float64 [N]: every vertex's betweenness over all N sources."""
    adj = sp.csr_matrix((np.ones(src.size), (src, dst)), shape=(n, n))
    fwd = adj.T.tocsr()
    blocks = [np.arange(s, min(n, s + BLOCK)) for s in range(0, n, BLOCK)]
    with ThreadPoolExecutor(max(1, threads)) as ex:
        parts = list(ex.map(lambda b: _block(fwd, adj, b, precision),
                            blocks))
    return np.sum(parts, axis=0)
