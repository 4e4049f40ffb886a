"""UTS jobs: count the nodes of one geometric tree per job.

An item is ``{"root_seed": r, "nodes": n}``: the root of the tree and
its size as the reference counted it when the catalogue was made (kept
for the reader; every run counts again).  The job is
``run_irregular(pool, uts_spec(UTSParams(...)))`` with the
configuration's task shape (``split_factor``, ``iters``).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Tuple

import reference

UNIT = "nodes"


class Jobs:
    def __init__(self, config: dict) -> None:
        self.config = config

    def params(self, item: dict):
        from repro.algorithms import UTSParams
        c = self.config
        return UTSParams(seed=int(item["root_seed"]), b0=float(c["b0"]),
                         max_depth=int(c["max_depth"]),
                         chunk=int(c["chunk"]))

    def spec(self, item: dict):
        from repro.algorithms import uts_spec
        from repro.core import TaskShape
        c = self.config
        return dataclasses.replace(
            uts_spec(self.params(item)),
            shape=TaskShape(split_factor=int(c["split_factor"]),
                            iters=int(c["iters"])))

    def output(self, result) -> int:
        return int(result.output)

    def work(self, item: dict, output: int) -> float:
        """Nodes counted by the job."""
        return float(output)

    def describe(self, item: dict, output: int) -> str:
        return f"root_seed={item['root_seed']} nodes={output}"

    def _counts(self, items: List[dict], precision: str) -> List[int]:
        c = self.config
        threads = os.cpu_count() or 1
        return [reference.uts_count(int(it["root_seed"]), float(c["b0"]),
                                    int(c["max_depth"]), threads, precision)
                for it in items]

    def compare(self, jobs: List[Tuple[dict, int]],
                precision: str = "float64") -> Tuple[Dict[str, float], int]:
        """Numbers compared against the reference, and the jobs that
        disagree.  ``node_count_gap``: the sum over jobs of
        |program's count - reference count|."""
        ref = self._counts([it for it, _ in jobs], precision)
        gaps = [abs(out - r) for (_, out), r in zip(jobs, ref)]
        return ({"node_count_gap": float(sum(gaps))},
                sum(1 for g in gaps if g))

    def control(self, items: List[dict]) -> Dict[str, float]:
        """The compared numbers with the float32 child count put in the
        program's place."""
        ctl = self._counts(items, "float32")
        return self.compare(list(zip(items, ctl)))[0]


#: the limit of each compared number: counts are exact
LIMITS = {"node_count_gap": 0.0}
