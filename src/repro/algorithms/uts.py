"""Unbalanced Tree Search on the elastic executor (paper §4.1.1, Listing 2).

UTS counts the nodes of a tree generated on the fly from SHA-1 digests:
child ``i`` of a node is ``SHA1(parent || be32(i))`` and the number of
children is Geometric(mean b0) with a depth cutoff.  The tree is wildly
unbalanced, which is the whole point — static partitioning loses.

Structure mirrors the paper exactly:

* a ``Bag`` encapsulates a frontier of unexplored subtrees;
* each task traverses at most ``iters`` nodes of its bag and returns the
  leftover bag (``RemoteUTSCallable``);
* the master re-splits leftover bags with the current split factor and
  re-dispatches; since the unified-pool redesign that loop is the
  generic ``repro.core.run_irregular`` driver and UTS is just the
  ``uts_spec`` WorkSpec below (``uts_parallel`` remains as a shim);
* the adaptive controller of §5.2 retunes (split_factor, iters) from the
  live concurrency level.

TPU adaptation: a task's traversal is *generation-vectorized* — the whole
frontier advances one generation per step through the batched SHA-1
Pallas kernel, instead of the canonical scalar DFS.  Node count semantics
are identical (each node expanded exactly once).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

from ..core import (
    Pool,
    StagedController,
    TaskShape,
    WorkSpec,
    run_irregular,
)
from ..core.telemetry import span
from ..kernels.dispatch import bucket, on_tpu, to_device, to_host
from ..kernels.uts_hash.ops import geometric_children, uts_child_digests
from ..kernels.uts_hash.numpy_impl import (
    geometric_children_np,
    root_digest,
    uts_child_digests_np,
)

__all__ = ["Bag", "UTSParams", "UTSResult", "expand_bag", "uts_spec",
           "uts_sequential", "uts_parallel", "expected_tree_size"]


@dataclass(frozen=True)
class UTSParams:
    seed: int = 19
    b0: float = 4.0
    max_depth: int = 18
    #: nodes expanded per vectorized generation step inside a task
    chunk: int = 8192


@dataclass
class Bag:
    """A frontier of unexplored nodes: digests [5, n] uint32, depths [n]."""

    digests: np.ndarray
    depths: np.ndarray

    @property
    def size(self) -> int:
        return int(self.depths.shape[0])

    @staticmethod
    def empty() -> "Bag":
        return Bag(np.zeros((5, 0), np.uint32), np.zeros((0,), np.int32))

    @staticmethod
    def root(params: UTSParams) -> "Bag":
        with span("uts.root"):
            return Bag(root_digest(params.seed), np.zeros((1,), np.int32))

    def split(self, k: int) -> List["Bag"]:
        """Resize into <= k sub-bags (paper's ``resizeBag``)."""
        if self.size == 0:
            return []
        k = max(1, min(k, self.size))
        cuts = np.array_split(np.arange(self.size), k)
        return [Bag(self.digests[:, ix], self.depths[ix])
                for ix in cuts if len(ix)]

    @staticmethod
    def merge(bags: List["Bag"]) -> "Bag":
        bags = [b for b in bags if b.size]
        if not bags:
            return Bag.empty()
        return Bag(np.concatenate([b.digests for b in bags], axis=1),
                   np.concatenate([b.depths for b in bags]))


def _expand_generation(digests: np.ndarray, depths: np.ndarray,
                       params: UTSParams) -> Tuple[np.ndarray, np.ndarray]:
    """Expand one generation of nodes -> (child_digests, child_depths).

    Both jitted stages are padded to *fixed* bucket sizes derived from
    ``params.chunk`` so an entire traversal compiles O(1) graphs (the
    frontier size is irregular by construction; without this every
    generation would recompile).  On the chip the host work between
    the kernel calls runs in ``uts.frontier`` spans.
    """
    n = depths.shape[0]
    if n == 0:
        return np.zeros((5, 0), np.uint32), np.zeros((0,), np.int32)
    chip = on_tpu()
    if chip:
        with span("uts.frontier"):
            # bucket-pad -> bounded set of compiled kernels; padding rows
            # sit at max_depth and thus produce zero children.
            nb = bucket(n, floor=min(params.chunk, 4096))
            dig_p = to_device(np.pad(digests, ((0, 0), (0, nb - n))))
            dep_p = to_device(np.pad(depths, (0, nb - n),
                                     constant_values=params.max_depth))
        with span("dispatch.geometric_children"):
            counts_d = geometric_children(dig_p, dep_p, b0=params.b0,
                                          max_depth=params.max_depth)
        counts = to_host(counts_d)[:n]
    else:
        counts = geometric_children_np(digests, depths, b0=params.b0,
                                       max_depth=params.max_depth)
    with span("uts.frontier"):
        total = int(counts.sum())
        if total == 0:
            return np.zeros((5, 0), np.uint32), np.zeros((0,), np.int32)
        parent_ix = np.repeat(np.arange(n), counts)
        # child index within each parent: 0..m_i-1
        offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
        child_ix = (np.arange(total) - offsets[parent_ix]).astype(np.uint32)
        parents = digests[:, parent_ix]
        child_depths = (depths[parent_ix] + 1).astype(np.int32)
    if not chip:
        return uts_child_digests_np(parents, child_ix), child_depths
    # chip: hash in fixed-size slices -> single compiled Pallas dispatch
    hb = 4 * min(params.chunk, 4096)
    outs = []
    for s in range(0, total, hb):
        e = min(s + hb, total)
        with span("uts.frontier"):
            par = to_device(np.pad(parents[:, s:e],
                                   ((0, 0), (0, hb - (e - s)))))
            cix = to_device(np.pad(child_ix[s:e], (0, hb - (e - s))))
        outs.append(to_host(uts_child_digests(par, cix,
                                              kept=e - s))[:, :e - s])
    with span("uts.frontier"):
        # free the generation's device buffers here rather than at the
        # return: a buffer's release can wait on the interpreter lock
        del dig_p, dep_p, counts_d, par, cix
        return np.concatenate(outs, axis=1), child_depths


def expand_bag(bag: Bag, iters: int,
               params: UTSParams) -> Tuple[int, Bag]:
    """Traverse up to ``iters`` nodes of ``bag``; return (count, leftover).

    This is the task body (``RemoteUTSCallable.call`` in Listing 2): a
    pure function of its inputs — stateless, hence re-dispatchable.
    LIFO order (children pushed on top) keeps the open frontier bounded
    the way the canonical DFS does, generation-vectorized in chunks.
    """
    count = 0
    stack = bag
    while count < iters and stack.size:
        with span("uts.merge"):
            budget = iters - count
            take = min(stack.size, budget, params.chunk)
            head = Bag(stack.digests[:, -take:], stack.depths[-take:])
            rest = Bag(stack.digests[:, :-take], stack.depths[:-take])
            count += take
        children, depths = _expand_generation(head.digests, head.depths,
                                              params)
        with span("uts.merge"):
            stack = Bag.merge([rest, Bag(children, depths)])
    return count, stack


def uts_sequential(params: UTSParams,
                   node_limit: Optional[int] = None) -> int:
    """Single-threaded reference count (paper's 'Sequential' row)."""
    count, leftover = expand_bag(Bag.root(params),
                                 node_limit or 2**62, params)
    if leftover.size:
        raise RuntimeError("node_limit hit before traversal finished")
    return count


@dataclass
class UTSResult:
    count: int
    wall_time_s: float
    tasks: int
    params: UTSParams
    peak_concurrency: int = 0
    controller_transitions: list = field(default_factory=list)

    @property
    def throughput(self) -> float:
        """Nodes per second (the paper's headline metric)."""
        return self.count / self.wall_time_s if self.wall_time_s else 0.0


def uts_spec(params: UTSParams) -> WorkSpec:
    """UTS as a declarative ``WorkSpec`` for ``run_irregular``.

    Work items are ``Bag`` frontiers; the task body traverses at most
    ``shape.iters`` nodes and returns ``(count, leftover)``; leftovers
    are re-split with the live split factor (paper's ``resizeBag``)."""

    def _resize(bag: Bag, shape: TaskShape) -> List[Bag]:
        return bag.split(shape.split_factor if bag.size > 1 else 1)

    def execute(bag: Bag, shape: TaskShape) -> Tuple[int, Bag]:
        return expand_bag(bag, shape.iters, params)

    def execute_batch(bags: List[Bag],
                      shape: TaskShape) -> List[Tuple[int, Bag]]:
        """Fused task body: the queued bags are merged into one frontier
        and expanded through a single sequence of vectorized kernel
        invocations with the batch's combined iteration budget.  Every
        node is still expanded exactly once, so the run's total count is
        identical to the per-task path; the leftover comes back on the
        first slot and is re-split by the driver's ``split`` hook."""
        merged = Bag.merge(list(bags))
        count, leftover = expand_bag(merged, shape.iters * len(bags),
                                     params)
        return ([(count, leftover)]
                + [(0, Bag.empty())] * (len(bags) - 1))

    def split(result: Tuple[int, Bag], shape: TaskShape) -> List[Bag]:
        _, leftover = result
        return _resize(leftover, shape) if leftover.size else []

    # WAL codecs (repro.chaos crash recovery): a bag is exactly its
    # digests + depths, both integer arrays, so the JSON round trip is
    # lossless and the frontier key is canonical
    def _enc_bag(bag: Bag) -> dict:
        return {"d": bag.digests.tolist(), "p": bag.depths.tolist()}

    def _dec_bag(enc: dict) -> Bag:
        return Bag(np.asarray(enc["d"], np.uint32).reshape(5, -1),
                   np.asarray(enc["p"], np.int32))

    return WorkSpec(
        name="uts",
        execute=execute,
        execute_batch=execute_batch,
        seed=lambda shape: _resize(Bag.root(params), shape),
        split=split,
        reduce=lambda total, result: total + result[0],
        init=lambda: 0,
        # int node counts: exact under any grouping, so sharded runs
        # (shards=K) are bit-identical to the single master
        merge=lambda a, b: a + b,
        cost_hint=lambda bag: float(bag.size),
        encode_item=_enc_bag,
        encode_result=lambda r: {"c": int(r[0]), **_enc_bag(r[1])},
        decode_result=lambda e: (e["c"], _dec_bag(e)),
        # checkpoint codecs: the bag encoding happens to be invertible
        # and the accumulator is an exact int, so UTS supports WAL
        # segment checkpointing (run_irregular checkpoint_every=)
        decode_item=_dec_bag,
        encode_state=lambda s: int(s),
        decode_state=lambda e: int(e),
        shape=TaskShape(split_factor=8, iters=50_000),
    )


def uts_parallel(
    executor: Pool,
    params: UTSParams,
    *,
    shape: TaskShape = TaskShape(split_factor=8, iters=50_000),
    controller: Optional[StagedController] = None,
    initial_split: Optional[int] = None,
) -> UTSResult:
    """Deprecated shim over ``run_irregular(pool, uts_spec(params))``.

    Kept for source compatibility with the per-algorithm master loops;
    new code should drive ``uts_spec`` directly (Listing 2's loop and
    the Listing 5 controller both live in ``repro.core.irregular``)."""
    warnings.warn(
        "uts_parallel is deprecated; use "
        "run_irregular(pool, uts_spec(params)) instead",
        DeprecationWarning, stacklevel=2)
    initial = (TaskShape(initial_split, shape.iters)
               if initial_split is not None else None)
    r = run_irregular(executor, uts_spec(params), shape=shape,
                      initial_shape=initial, controller=controller)
    return UTSResult(
        count=r.output,
        wall_time_s=r.wall_time_s,
        tasks=r.tasks,
        params=params,
        peak_concurrency=r.peak_concurrency,
        controller_transitions=r.controller_transitions,
    )


def expected_tree_size(b0: float, depth: int) -> float:
    """E[#nodes] = sum_{l=0}^{depth} b0^l — the Table 1 growth law."""
    return (b0 ** (depth + 1) - 1) / (b0 - 1)
