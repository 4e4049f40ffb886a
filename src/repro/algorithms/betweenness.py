"""Betweenness Centrality (SSCA2 kernel 4) on the elastic executor (§4.1.3).

Brandes' algorithm over an unweighted R-MAT digraph.  The vertex set is
statically partitioned into T tasks after a random permutation (paper:
T=128, seed=2, R-MAT probs (0.55, 0.1, 0.1, 0.25)); each task computes
the dependency contributions of its source block and the master sums the
partial betweenness maps.

TPU adaptation: the per-source forward/backward sweeps of Brandes are
*batched over sources* and expressed as dense frontier-matrix products
(level-synchronous BFS as sigma @ A on the MXU), instead of the scalar
queue-based X10/Java loops.  Each task re-generates the graph locally
(paper Listing 4 line 44: the graph is too large to ship to a function,
so functions rebuild it from the R-MAT parameters) — kept here behind
``regenerate_graph`` to reproduce the shared-resources experiment.  Such
a task draws the R-MAT edge samples on the host (``rmat_edges``), uploads
that edge list and scatters it into the dense adjacency on the device
(``rmat_adjacency``); without ``regenerate_graph`` the master ships the
dense adjacency that ``rmat_graph`` scatters on the host.

``bc_reference`` is the plain float64 Brandes the pool is tested against.
"""
from __future__ import annotations

import functools
import time
import warnings
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core import Pool, TaskShape, WorkSpec, run_irregular
from ..core.telemetry import count, enabled, span
from ..kernels.dispatch import to_device, to_host

__all__ = ["RMATParams", "rmat_edges", "rmat_graph", "rmat_adjacency",
           "bc_batch", "bc_reference",
           "bc_single_node", "bc_spec", "betweenness_centrality",
           "BCResult"]

_INF = np.int32(2**30)
# f32 products at full precision: at default precision the TPU multiplies
# in one bf16 pass, which rounds path counts sigma above 256
_EXACT = jax.lax.Precision.HIGHEST
#: sources per level-synchronous pass of ``bc_reference``
_REF_BLOCK = 256


@dataclass(frozen=True)
class RMATParams:
    scale: int = 10                    # N = 2**scale vertices
    edge_factor: int = 8               # M = edge_factor * N edge samples
    a: float = 0.55
    b: float = 0.10
    c: float = 0.10
    d: float = 0.25
    seed: int = 2

    @property
    def n_vertices(self) -> int:
        return 1 << self.scale


def rmat_edges(p: RMATParams,
               permute: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """The R-MAT digraph's edge samples: int32 (src, dst), each of length
    ``edge_factor * N``.

    Recursive-matrix sampling (Chakrabarti et al.): one uniform draw per
    sample and bit level picks a quadrant, the first draw the highest
    bit; then the vertices are permuted (paper §4.1.3: permutation makes
    the static partition more homogeneous — but still imbalanced).
    Duplicates and self-loops stay in the arrays, so their length
    depends only on the scale; the adjacency keeps a duplicate once and
    drops a self-loop (``rmat_graph``, ``rmat_adjacency``).
    """
    rng = np.random.RandomState(p.seed)
    n = p.n_vertices
    m = p.edge_factor * n
    # row k is the k-th bit level's draws, as k successive rand(m) calls
    r = rng.rand(p.scale, m)
    ab = p.a + p.b
    src_bit = r >= ab                                       # quadrant c or d
    dst_bit = ((r >= p.a) & (r < ab)) | (r >= ab + p.c)     # quadrant b or d
    weight = (1 << np.arange(p.scale - 1, -1, -1, dtype=np.int32))[:, None]
    src = (src_bit * weight).sum(axis=0, dtype=np.int32)
    dst = (dst_bit * weight).sum(axis=0, dtype=np.int32)
    if permute:
        perm = rng.permutation(n).astype(np.int32)
        src, dst = perm[src], perm[dst]
    return src, dst


def rmat_graph(p: RMATParams, permute: bool = True) -> np.ndarray:
    """Dense adjacency (float32 [N, N]) of the R-MAT digraph, scattered
    on the host from ``rmat_edges``: duplicates kept once, self-loops
    dropped."""
    src, dst = rmat_edges(p, permute)
    keep = src != dst
    adj = np.zeros((p.n_vertices, p.n_vertices), np.float32)
    adj[src[keep], dst[keep]] = 1.0
    return adj


@functools.partial(jax.jit, static_argnames=("n",))
def rmat_adjacency(edges: jax.Array, n: int) -> jax.Array:
    """``rmat_graph``'s adjacency scattered on the device.

    edges: [2, M] int32, ``rmat_edges``' (src, dst) stacked
    returns [N, N] float32: 1 at each sample's (src, dst), a self-loop
            sent to row N and dropped there
    """
    src, dst = edges[0], edges[1]
    src = jnp.where(src == dst, n, src)
    return jnp.zeros((n, n), jnp.float32).at[src, dst].set(
        1.0, mode="drop")


@functools.partial(jax.jit, static_argnames=("max_levels",))
def bc_batch(adj: jax.Array, sources: jax.Array,
             max_levels: Optional[int] = None) -> jax.Array:
    """Brandes dependency sums for a batch of sources, and the levels swept.

    adj:     [N, N] float32 dense adjacency (directed, unweighted)
    sources: [S] int32 source vertex ids
    returns  [N + 1] float32 — the sum over the batch of the dependency
             scores delta of each vertex, then the number of BFS levels
             the forward sweep expanded (levels 0 to the batch's depth;
             at most N, so exact in float32).  The two share one array
             so that one transfer brings both to the host.
    """
    n = adj.shape[0]
    s = sources.shape[0]
    levels = max_levels or n

    src_onehot = jax.nn.one_hot(sources, n, dtype=jnp.float32)  # [S, N]
    dist0 = jnp.where(src_onehot > 0, 0, _INF).astype(jnp.int32)
    sigma0 = src_onehot

    # -- forward: level-synchronous BFS with path counting ----------------
    def fwd_cond(carry):
        level, dist, sigma, frontier_any = carry
        return jnp.logical_and(frontier_any, level < levels)

    def fwd_body(carry):
        level, dist, sigma, _ = carry
        frontier = (dist == level).astype(jnp.float32)          # [S, N]
        reach = jnp.dot(sigma * frontier, adj, precision=_EXACT)  # [S, N]
        unvisited = dist == _INF
        newfront = jnp.logical_and(unvisited, reach > 0)
        dist = jnp.where(newfront, level + 1, dist)
        sigma = sigma + jnp.where(newfront, reach, 0.0)
        return level + 1, dist, sigma, jnp.any(newfront)

    level, dist, sigma, _ = jax.lax.while_loop(
        fwd_cond, fwd_body, (jnp.int32(0), dist0, sigma0, jnp.bool_(True)))

    # -- backward: dependency accumulation --------------------------------
    safe_sigma = jnp.where(sigma > 0, sigma, 1.0)

    def bwd_body(carry):
        lvl, delta = carry
        w_mask = (dist == lvl).astype(jnp.float32)
        coeff = w_mask * (1.0 + delta) / safe_sigma             # [S, N]
        back = jnp.dot(coeff, adj.T, precision=_EXACT)          # [S, N]
        v_mask = (dist == lvl - 1).astype(jnp.float32)
        delta = delta + v_mask * sigma * back
        return lvl - 1, delta

    def bwd_cond(carry):
        lvl, _ = carry
        return lvl >= 1

    _, delta = jax.lax.while_loop(
        bwd_cond, bwd_body, (level, jnp.zeros((s, n), jnp.float32)))

    # exclude the source itself from its own dependency sum
    delta = delta * (1.0 - src_onehot)
    return jnp.concatenate([delta.sum(axis=0),
                            level[None].astype(jnp.float32)])


def bc_reference(adj: np.ndarray) -> np.ndarray:
    """Betweenness of every vertex by Brandes' algorithm in float64 numpy.

    SSCA#2 kernel 4 (Bader & Madduri): for each vertex v, the sum over
    sources s != v of the dependency delta_s(v) = sum over targets t of
    sigma_st(v) / sigma_st, with delta_s accumulated back from the
    farthest BFS level (Brandes 2001).  Departures from the kernel's
    definition, which the program shares:

    * directed: paths follow ``adj[u, v] != 0`` from u to v, and no
      score is halved;
    * unweighted: no edge is filtered by its weight, every edge counts
      as length 1 (duplicate R-MAT samples are one edge, self-loops are
      dropped by ``rmat_graph``);
    * exact: every vertex is a source (no sampling of K4approx sources);
    * a source is excluded from its own dependency: delta_s(s) is 0.

    Sources are taken 256 at a time, level-synchronously: one product
    with the adjacency expands the frontiers of all of them.
    """
    a = (np.asarray(adj) != 0).astype(np.float64)
    n = a.shape[0]
    out = np.zeros(n, np.float64)
    for start in range(0, n, _REF_BLOCK):
        src = np.arange(start, min(n, start + _REF_BLOCK))
        rows = np.arange(src.size)
        dist = np.full((src.size, n), -1, np.int64)
        sigma = np.zeros((src.size, n), np.float64)
        dist[rows, src] = 0
        sigma[rows, src] = 1.0
        depth = 0
        while True:
            reach = (sigma * (dist == depth)) @ a
            new = (dist < 0) & (reach > 0)
            if not new.any():
                break
            depth += 1
            dist[new] = depth
            sigma[new] = reach[new]
        delta = np.zeros_like(sigma)
        safe_sigma = np.where(sigma > 0, sigma, 1.0)
        for lvl in range(depth, 0, -1):
            coeff = np.where(dist == lvl, (1.0 + delta) / safe_sigma, 0.0)
            delta += np.where(dist == lvl - 1, sigma * (coeff @ a.T), 0.0)
        delta[rows, src] = 0.0
        out += delta.sum(axis=0)
    return out


def bc_single_node(adj: np.ndarray, n_tasks: int = 1) -> np.ndarray:
    """All-sources BC through ``bc_batch`` on one device, without the
    pool ('parallel VM' baseline); the plain reference is
    ``bc_reference``."""
    n = adj.shape[0]
    adj_j = jnp.asarray(adj)
    out = np.zeros(n, np.float64)
    for block in np.array_split(np.arange(n, dtype=np.int32),
                                max(1, n_tasks)):
        out += np.asarray(bc_batch(adj_j, jnp.asarray(block)),
                          np.float64)[:n]
    return out


@functools.partial(jax.jit, static_argnames=("n",))
def _bc_batch_from_edges(edges: jax.Array, sources: jax.Array,
                         n: int) -> jax.Array:
    """``bc_batch`` over ``rmat_adjacency(edges, n)``, as one program."""
    return bc_batch(rmat_adjacency(edges, n), sources)


def _bc_task(p: RMATParams, sources: np.ndarray,
             adj: Optional[np.ndarray]) -> np.ndarray:
    """Task body (``ServerlessCallable`` of Listing 4).

    Without a shipped ``adj`` the task draws its graph's edge samples,
    uploads them ([2, edge_factor * N] int32) and scatters them into the
    adjacency on the device, in the program that runs the sweeps; with
    one it uploads that dense adjacency.

    With the recorder of ``repro.core.telemetry`` on: spans ``bc.graph``
    (the edge draws), ``bc.upload`` (edges or adjacency, and sources, to
    the device), ``dispatch.bc_batch`` (the build's and sweeps' dispatch)
    and ``device.pull``; counters ``bc.sources``, ``bc.levels`` (BFS
    levels the forward sweep expanded) and ``bc.edges`` (edge samples
    scattered), beside ``h2d_bytes`` and ``d2h_bytes``."""
    if adj is None:
        with span("bc.graph"):
            # line 44: generateGraph() inside the function
            graph = np.stack(rmat_edges(p))
        sweep = functools.partial(_bc_batch_from_edges, n=p.n_vertices)
    else:
        graph, sweep = adj, bc_batch
    with span("bc.upload"):
        graph_d = to_device(graph)
        sources_d = to_device(sources, np.int32)
    with span("dispatch.bc_batch"):
        out = sweep(graph_d, sources_d)
    out = to_host(out)
    if enabled():
        count("bc.sources", sources.size)
        count("bc.levels", int(out[-1]))
        if adj is None:
            count("bc.edges", graph.shape[1])
    return out[:-1]


@dataclass
class BCResult:
    betweenness: np.ndarray
    wall_time_s: float
    tasks: int

    @property
    def throughput(self) -> float:
        """Vertices (sources) processed per second."""
        return self.betweenness.shape[0] / self.wall_time_s \
            if self.wall_time_s else 0.0


def bc_spec(
    p: RMATParams,
    *,
    n_tasks: int = 128,
    regenerate_graph: bool = True,
    adj: Optional[np.ndarray] = None,
) -> WorkSpec:
    """BC as a declarative ``WorkSpec``: a static map-reduce.

    Paper Listing 4 — the vertex set is partitioned into ``n_tasks``
    source blocks; each task runs batched Brandes for its block and the
    master aggregates the ``globalBetweennessMap`` (line 34) in the
    ``reduce`` hook.  With ``regenerate_graph`` each function rebuilds
    the graph from the R-MAT parameters (line 44) and ``adj`` is unused;
    without it each function is shipped ``adj``, or ``rmat_graph(p)``
    built here once."""
    shipped = None
    if not regenerate_graph:
        shipped = rmat_graph(p) if adj is None else adj
    n = p.n_vertices if shipped is None else shipped.shape[0]

    def seed(shape: TaskShape) -> List[np.ndarray]:
        return [block for block in
                np.array_split(np.arange(n, dtype=np.int32), n_tasks)
                if len(block)]

    def execute(block: np.ndarray,
                shape: TaskShape) -> Tuple[int, np.ndarray]:
        # keyed contribution: (first source id, partial map).  Floating
        # sums are order-sensitive, so partials are collected keyed and
        # summed in canonical key order by ``finalize`` — the final
        # betweenness is then bit-identical no matter which master
        # shard or completion order produced each partial.
        return int(block[0]), _bc_task(p, block, shipped)

    def execute_batch(blocks: List[np.ndarray],
                      shape: TaskShape) -> List[Tuple[int, np.ndarray]]:
        """Fused task body: the queued source blocks are stacked into
        one ``bc_batch`` invocation (one forward/backward sweep over the
        union of sources).  The summed dependency map lands on the first
        slot keyed by the first block; the remaining slots carry exact
        zero contributions under their own keys."""
        sources = np.concatenate([np.asarray(b) for b in blocks])
        partial = _bc_task(p, sources, shipped)
        return ([(int(blocks[0][0]), partial)]
                + [(int(b[0]), np.zeros(n, partial.dtype))
                   for b in blocks[1:]])

    def finalize(parts: List[Tuple[int, np.ndarray]]) -> np.ndarray:
        out = np.zeros(n, np.float64)
        for _, partial in sorted(parts, key=lambda kp: kp[0]):
            out += partial
        return out

    # WAL codecs (repro.chaos crash recovery): blocks key on their int
    # ids; a partial's float values survive the JSON trip exactly
    # (binary float -> shortest-repr decimal -> same binary float), so
    # recovered runs stay bit-identical through ``finalize``'s
    # canonical-order sum
    return WorkSpec(
        name="betweenness_centrality",
        execute=execute,
        execute_batch=execute_batch,
        seed=seed,
        reduce=lambda parts, keyed: parts + [keyed],
        init=list,
        finalize=finalize,
        merge=lambda a, b: a + b,
        cost_hint=lambda block: float(len(block)),
        encode_item=lambda block: np.asarray(block).tolist(),
        encode_result=lambda r: {"k": int(r[0]), "v": r[1].tolist(),
                                 "dt": str(r[1].dtype)},
        decode_result=lambda e: (e["k"],
                                 np.asarray(e["v"], np.dtype(e["dt"]))),
    )


def betweenness_centrality(
    executor: Pool,
    p: RMATParams,
    *,
    n_tasks: int = 128,
    regenerate_graph: bool = True,
    adj: Optional[np.ndarray] = None,
) -> BCResult:
    """Deprecated shim over ``run_irregular(pool, bc_spec(p, ...))``."""
    warnings.warn(
        "betweenness_centrality is deprecated; use "
        "run_irregular(pool, bc_spec(p, ...)) instead",
        DeprecationWarning, stacklevel=2)
    t0 = time.monotonic()
    r = run_irregular(executor, bc_spec(
        p, n_tasks=n_tasks, regenerate_graph=regenerate_graph, adj=adj))
    return BCResult(
        betweenness=r.output,
        wall_time_s=time.monotonic() - t0,
        tasks=r.tasks,
    )
