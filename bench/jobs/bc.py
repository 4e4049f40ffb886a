"""Betweenness-centrality jobs: exact betweenness of one R-MAT graph per
job (SSCA#2 kernel 4).

An item is ``{"rmat_seed": s}``: the seed of the R-MAT generator, so
the graph.  The job is ``run_irregular(pool, bc_spec(RMATParams(...)))``
with the configuration's ``n_tasks`` source blocks; each task rebuilds
the graph from its parameters (``regenerate_graph``).  Its work is the
graph's N vertices, each taken once as a source.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import bc_reference

UNIT = "sources"


class Jobs:
    def __init__(self, config: dict) -> None:
        self.config = config

    def params(self, item: dict):
        from repro.algorithms import RMATParams
        c = self.config
        return RMATParams(scale=int(c["scale"]),
                          edge_factor=int(c["edge_factor"]),
                          a=float(c["a"]), b=float(c["b"]), c=float(c["c"]),
                          d=float(c["d"]), seed=int(item["rmat_seed"]))

    def spec(self, item: dict):
        from repro.algorithms import bc_spec
        c = self.config
        return bc_spec(self.params(item), n_tasks=int(c["n_tasks"]),
                       regenerate_graph=bool(c["regenerate_graph"]))

    def output(self, result) -> np.ndarray:
        return np.array(result.output, np.float64, copy=True)

    def work(self, item: dict, output: np.ndarray) -> float:
        """Sources whose dependencies the job summed: every vertex."""
        return float(output.size)

    def describe(self, item: dict, output: np.ndarray) -> str:
        return (f"rmat_seed={item['rmat_seed']} vertices={output.size} "
                f"max_bc={float(output.max())!r}")

    def _betweenness(self, items: List[dict],
                     precision: str) -> List[np.ndarray]:
        """The reference's betweenness for each item, computed once for
        each distinct graph."""
        c = self.config
        found: Dict[int, np.ndarray] = {}
        for it in items:
            seed = int(it["rmat_seed"])
            if seed not in found:
                src, dst = bc_reference.rmat_edges(
                    int(c["scale"]), int(c["edge_factor"]), float(c["a"]),
                    float(c["b"]), float(c["c"]), seed)
                found[seed] = bc_reference.betweenness(
                    src, dst, 1 << int(c["scale"]), precision)
        return [found[int(it["rmat_seed"])] for it in items]

    def compare(self, jobs: List[Tuple[dict, np.ndarray]],
                precision: str = "float64") -> Tuple[Dict[str, float], int]:
        """Numbers compared against the reference, and the jobs that
        disagree.  ``bc_max_rel_err``: the largest, over jobs and
        vertices, of |program - reference| / max(reference, 1)."""
        ref = self._betweenness([it for it, _ in jobs], precision)
        errs = [float(np.max(np.abs(out - r) / np.maximum(r, 1.0)))
                for (_, out), r in zip(jobs, ref)]
        return ({"bc_max_rel_err": max(errs)},
                sum(1 for e in errs if e > LIMITS["bc_max_rel_err"]))

    def control(self, items: List[dict]) -> Dict[str, float]:
        """The compared numbers with the reference's products rounded to
        bfloat16 operands put in the program's place."""
        ctl = self._betweenness(items, "bfloat16")
        return self.compare(list(zip(items, ctl)))[0]


#: The program sums path counts and dependencies in float32 with
#: products at full precision: on one TPU v5e it reads 1.49e-7 at R-MAT
#: scale 12, seed 2, and the control (each product's operands rounded
#: to bfloat16, as one pass at default precision does) reads 5.7e-3.
#: 1e-5 leaves the program 67 times of room and rejects the control by
#: a factor of 570.
LIMITS = {"bc_max_rel_err": 1e-5}
