"""Run the paper's three irregular jobs once on one TPU chip and check them.

    python chip_smoke.py

Each job goes through the entry points a user calls, ``make_pool`` and
``run_irregular``, on a real-clock thread pool whose task bodies run on
the chip, with the paper's parameters (``repro.configs.paper_workloads``).
Where a size is cut, a ``cut`` line says what and why.

* UTS: node count equal to a numpy-only traversal on the host.
* Mariani-Silver: image equal pixel for pixel to the pure-jnp reference
  render, and the Pallas kernel equal to that reference on every tile of
  the paper's 4096x4096 plane.
* Betweenness centrality: within a stated tolerance of networkx's exact
  Brandes (directed, unnormalised).

Each phase prints its wall, set-up, run and reference seconds, its task
count, its check, and the kernel compile-log entries it added.  Any
failed check, a phase still running after ``BUDGET_S``, or a first
device that is not a TPU ends the script with a non-zero exit before the
result line.  The last line of the output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.algorithms import (MSParams, RMATParams, UTSParams,  # noqa: E402
                              bc_batch, bc_spec, ms_spec, naive_render,
                              plane_coords, rmat_graph, uts_spec)
from repro.configs.paper_workloads import (BC_PAPER,  # noqa: E402
                                           BC_PAPER_TASKS, MS_PAPER_SD64,
                                           UTS_PAPER)
from repro.core import make_pool, run_irregular  # noqa: E402
from repro.kernels.dispatch import (compile_log,  # noqa: E402
                                    enable_compile_cache)
from repro.kernels.mandelbrot.ops import mandelbrot  # noqa: E402
from repro.kernels.uts_hash.numpy_impl import (  # noqa: E402
    geometric_children_np, uts_child_digests_np)

#: worker threads of the pool; the tasks share the one chip
WORKERS = 8
#: the repository's own tolerance against networkx (tests/test_betweenness.py)
BC_RTOL, BC_ATOL = 1e-4, 1e-3
#: lanes per host thread in the numpy UTS reference
_HOST_SLICE = 1 << 18
#: seconds ``main`` may take; a pool run still going past this raises
#: instead of being killed from outside
BUDGET_S = 1100.0


def _left(deadline: float | None) -> float | None:
    """Seconds until ``deadline`` (a ``time.monotonic`` instant), or None."""
    return None if deadline is None else deadline - time.monotonic()


class SmokeFailure(RuntimeError):
    """A phase's output disagrees with its reference."""


def report(phase: str, **fields) -> None:
    print(phase, " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _log_entries() -> set:
    return {(op, e) for op, entries in compile_log().items() for e in entries}


def _report_compiles(phase: str, before: set) -> None:
    for op, (backend, static, sig) in sorted(_log_entries() - before,
                                             key=repr):
        report(f"{phase} compile_log", op=op, backend=backend,
               static=static, shapes=sig)


def _slices(n: int):
    return [slice(s, min(s + _HOST_SLICE, n))
            for s in range(0, n, _HOST_SLICE)]


def uts_host_count(params: UTSParams, threads: int) -> int:
    """Nodes of the UTS tree, counted on the host with numpy alone.

    Level by level: every node of a generation has the same depth, and
    a node at ``max_depth`` has no children, so the last generation is
    counted from its parents' child counts and never hashed."""
    frontier = uts_child_digests_np(np.zeros((5, 1), np.uint32),
                                    np.array([params.seed], np.uint32))
    count = 1
    with ThreadPoolExecutor(threads) as ex:
        for depth in range(params.max_depth):
            n = frontier.shape[1]
            level = np.full(n, depth, np.int32)
            counts = np.concatenate(list(ex.map(
                lambda s: geometric_children_np(
                    frontier[:, s], level[s], b0=params.b0,
                    max_depth=params.max_depth),
                _slices(n))))
            total = int(counts.sum())
            count += total
            if total == 0 or depth + 1 == params.max_depth:
                break
            parent_ix = np.repeat(np.arange(n), counts)
            first_child = np.cumsum(counts) - counts
            child_ix = (np.arange(total)
                        - first_child[parent_ix]).astype(np.uint32)
            frontier = np.concatenate(list(ex.map(
                lambda s: uts_child_digests_np(frontier[:, parent_ix[s]],
                                               child_ix[s]),
                _slices(total))), axis=1)
    return count


def run_uts(params: UTSParams, warmup_depth: int, workers: int,
            deadline: float | None = None) -> None:
    """UTS on the pool, node count against the host-only traversal."""
    t_phase = time.perf_counter()
    before = _log_entries()
    with make_pool("local", max_concurrency=workers) as pool:
        t0 = time.perf_counter()
        run_irregular(pool, uts_spec(
            dataclasses.replace(params, max_depth=warmup_depth)),
            timeout=_left(deadline))
        setup = time.perf_counter() - t0
        warm = _log_entries()
        t0 = time.perf_counter()
        res = run_irregular(pool, uts_spec(params), timeout=_left(deadline))
        run = time.perf_counter() - t0
    t0 = time.perf_counter()
    expected = uts_host_count(params, os.cpu_count() or 1)
    ref = time.perf_counter() - t0
    report("uts", wall_s=time.perf_counter() - t_phase, setup_s=setup,
           run_s=run, ref_s=ref, tasks=res.tasks, nodes=res.output,
           host_nodes=expected, compiles_in_run=len(_log_entries() - warm))
    _report_compiles("uts", before)
    if res.output != expected:
        raise SmokeFailure(f"uts: {res.output} nodes on the pool, "
                           f"{expected} on the host")
    report("uts check", ok=True, nodes=expected)


def run_ms(params: MSParams, warmup: MSParams, workers: int,
           kernel_plane: MSParams | None = None,
           kernel_backend: str = "tpu-pallas",
           deadline: float | None = None) -> None:
    """Mariani-Silver on the pool, against the pure-jnp reference render.

    ``warmup`` is a smaller image cut into rectangles of the same sizes,
    so it compiles every kernel shape the measured run dispatches.  The
    kernel alone is also checked against the reference on every
    (256, 256) tile of ``kernel_plane`` (the run's own image if None)."""
    t_phase = time.perf_counter()
    before = _log_entries()
    with make_pool("local", max_concurrency=workers) as pool:
        t0 = time.perf_counter()
        run_irregular(pool, ms_spec(warmup), timeout=_left(deadline))
        setup = time.perf_counter() - t0
        warm = _log_entries()
        t0 = time.perf_counter()
        res = run_irregular(pool, ms_spec(params), timeout=_left(deadline))
        run = time.perf_counter() - t0
    compiles_in_run = len(_log_entries() - warm)
    t0 = time.perf_counter()
    oracle = naive_render(params)
    ref = time.perf_counter() - t0
    plane = kernel_plane or params
    plane_oracle = oracle if plane == params else naive_render(plane)
    tiles = np.asarray(mandelbrot(*plane_coords(plane), plane.max_dwell,
                                  backend=kernel_backend))
    differing = int(np.count_nonzero(res.output["image"] != oracle))
    kernel_differing = int(np.count_nonzero(tiles != plane_oracle))
    report("ms", wall_s=time.perf_counter() - t_phase, setup_s=setup,
           run_s=run, ref_s=ref, tasks=res.tasks,
           filled_px=res.output["filled"],
           evaluated_px=res.output["evaluated"],
           compiles_in_run=compiles_in_run)
    _report_compiles("ms", before)
    report("ms check", image_differing_px=differing, pixels=oracle.size,
           kernel_differing_px=kernel_differing,
           kernel_plane=f"{plane.width}x{plane.height}")
    if kernel_differing:
        raise SmokeFailure(f"ms: the {kernel_backend} kernel differs from "
                           f"mandelbrot_ref on {kernel_differing} pixels")
    if differing:
        # every pixel the run evaluated came from the kernel checked
        # above, so what differs was filled from a uniform border
        report("ms check", note="border-fill rule differs from the "
               "per-pixel render", differing_px=differing)
    report("ms check", ok=True)


def run_bc(params: RMATParams, n_tasks: int, workers: int,
           deadline: float | None = None) -> None:
    """Betweenness centrality on the pool, against networkx's Brandes."""
    import jax.numpy as jnp
    import networkx as nx

    t_phase = time.perf_counter()
    adj = rmat_graph(params)
    n = adj.shape[0]
    t0 = time.perf_counter()
    np.asarray(bc_batch(jnp.asarray(adj),
                        jnp.arange(n // n_tasks, dtype=jnp.int32)))
    setup = time.perf_counter() - t0
    with make_pool("local", max_concurrency=workers) as pool:
        t0 = time.perf_counter()
        res = run_irregular(pool, bc_spec(params, n_tasks=n_tasks),
                                timeout=_left(deadline))
        run = time.perf_counter() - t0
    t0 = time.perf_counter()
    graph = nx.from_numpy_array(adj, create_using=nx.DiGraph)
    brandes = nx.betweenness_centrality(graph, normalized=False)
    expected = np.array([brandes[v] for v in range(n)])
    ref = time.perf_counter() - t0
    got = res.output
    err = np.abs(got - expected)
    report("bc", wall_s=time.perf_counter() - t_phase, setup_s=setup,
           run_s=run, ref_s=ref, tasks=res.tasks, vertices=n,
           edges=int(adj.sum()))
    report("bc check", rtol=BC_RTOL, atol=BC_ATOL,
           max_abs_err=float(err.max()),
           max_rel_err=float((err / np.maximum(np.abs(expected),
                                               BC_ATOL)).max()),
           max_bc=float(expected.max()))
    if not np.allclose(got, expected, rtol=BC_RTOL, atol=BC_ATOL):
        raise SmokeFailure("bc: outside tolerance of networkx's Brandes")
    report("bc check", ok=True)


def check_kernel_backends() -> None:
    """The main path's kernels ran compiled for the chip, and only so."""
    log = compile_log()
    for op in ("uts_hash", "mandelbrot"):
        backends = sorted({backend for backend, _, _ in log.get(op, ())})
        report("compile_log", op=op, backends=backends,
               shapes=len(log.get(op, ())))
        if backends != ["tpu-pallas"]:
            raise SmokeFailure(f"{op} ran as {backends}, "
                               f"not only as tpu-pallas")


def main() -> None:
    deadline = time.monotonic() + BUDGET_S
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found; JAX's first device is "
                 f"{device.platform!r}")
    cache = Path(enable_compile_cache())
    # entries already there: whether set-up times below are warm or cold
    report("device", platform=device.platform, kind=repr(device.device_kind),
           count=len(jax.devices()), compile_cache=cache,
           cache_entries=len(list(cache.iterdir())) if cache.is_dir() else 0)

    uts = dataclasses.replace(UTS_PAPER, max_depth=14)
    report("cut", job="uts", max_depth=f"{UTS_PAPER.max_depth}->14",
           why="14 is the shallowest depth of the paper's Table 1, about "
               "1.3e8 nodes; depth 18 is 256 times as many nodes")
    ms = dataclasses.replace(MS_PAPER_SD64, max_dwell=4096, width=512,
                             height=512, initial_subdivision=8)
    report("cut", job="ms", max_dwell=f"{MS_PAPER_SD64.max_dwell}->4096",
           why="the plain reference iterates every pixel to max_dwell, "
               "and 5e6 is 1221 times as many iterations as 4096")
    report("cut", job="ms",
           size=f"{MS_PAPER_SD64.width}x{MS_PAPER_SD64.height} sd "
                f"{MS_PAPER_SD64.initial_subdivision}->512x512 sd 8",
           why="one task per rectangle, each costing milliseconds of "
               "host time: about 4.8e5 tasks at 4096^2 against 2.2e4 "
               "here; the rectangles keep the paper's 64 pixels, depth 5 "
               "and split 2")
    bc = dataclasses.replace(BC_PAPER, scale=12)
    report("cut", job="bc", scale=f"{BC_PAPER.scale}->12",
           why="the dense adjacency is 64 GiB at scale 17 and one chip "
               "holds 16 GB; at 12 it is 64 MiB and networkx's exact "
               "Brandes finishes in about a minute")

    run_uts(uts, warmup_depth=9, workers=WORKERS, deadline=deadline)
    # one 64-pixel rectangle: every kernel shape the image dispatches
    run_ms(ms, dataclasses.replace(ms, width=64, height=64,
                                   initial_subdivision=1),
           workers=WORKERS,
           kernel_plane=dataclasses.replace(MS_PAPER_SD64, max_dwell=4096),
           deadline=deadline)
    run_bc(bc, n_tasks=BC_PAPER_TASKS, workers=WORKERS, deadline=deadline)
    check_kernel_backends()

    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
