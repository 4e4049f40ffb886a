"""Mariani-Silver jobs: render one rectangle of the paper's plane per job.

An item is ``{"crop": [cx, cy]}``: the rectangle at column cx, row cy of
the plane's ``initial_subdivision`` x ``initial_subdivision`` grid.  The
job renders it as an image of its own at the plane's pixel pitch, from
one initial rectangle, down to ``max_depth`` with ``split`` x ``split``
children: ``run_irregular(pool, ms_spec(MSParams(...)))``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import reference

UNIT = "px"


class Jobs:
    def __init__(self, config: dict) -> None:
        self.config = config
        c = config
        if c["width"] % c["initial_subdivision"] or \
                c["height"] != c["width"]:
            raise ValueError("the plane must be square and cut into "
                             "whole rectangles")
        if c["rects_per_job"] != 1:
            raise ValueError("a job renders one rectangle of the plane; "
                             f"rects_per_job is {c['rects_per_job']}")
        self.size = c["width"] // c["initial_subdivision"]
        self.plane = {k: c[k] for k in ("x0", "y0", "x1", "y1",
                                        "width", "height")}

    def crop(self, item: dict) -> dict:
        cx, cy = item["crop"]
        return reference.crop_bounds(self.plane, int(cx), int(cy), self.size)

    def params(self, item: dict):
        from repro.algorithms import MSParams
        b = self.crop(item)
        c = self.config
        return MSParams(width=self.size, height=self.size,
                        max_dwell=int(c["max_dwell"]), x0=b["x0"],
                        y0=b["y0"], x1=b["x1"], y1=b["y1"],
                        split=int(c["split"]), max_depth=int(c["max_depth"]),
                        initial_subdivision=1)

    def spec(self, item: dict):
        from repro.algorithms import ms_spec
        return ms_spec(self.params(item))

    def output(self, result) -> np.ndarray:
        return np.array(result.output["image"], copy=True)

    def work(self, item: dict, output: np.ndarray) -> float:
        """Image pixels delivered by the job."""
        return float(output.size)

    def describe(self, item: dict, output: np.ndarray) -> str:
        b = self.crop(item)
        return (f"crop={item['crop'][0]},{item['crop'][1]} "
                f"x=[{b['x0']!r},{b['x1']!r}) y=[{b['y0']!r},{b['y1']!r})")

    def _images(self, items: List[dict], precision: str) -> List[np.ndarray]:
        c = self.config
        dwell = reference.crop_dwells([self.crop(it) for it in items],
                                      int(c["max_dwell"]), precision)
        return [reference.mariani_silver(d, int(c["max_depth"]),
                                         int(c["split"]))[0]
                for d in dwell]

    def compare(self, jobs: List[Tuple[dict, np.ndarray]],
                precision: str = "float32") -> Tuple[Dict[str, float], int]:
        """Numbers compared against the reference, and the jobs that
        disagree.  ``pixels_differing``: image pixels, filled and
        evaluated alike, that differ from the reference's image."""
        ref = self._images([it for it, _ in jobs], precision)
        bad = [int(np.count_nonzero(out != r))
               for (_, out), r in zip(jobs, ref)]
        return ({"pixels_differing": float(sum(bad))},
                sum(1 for b in bad if b))

    def control(self, items: List[dict]) -> Dict[str, float]:
        """The compared numbers with the bfloat16 render put in the
        program's place."""
        ctl = self._images(items, "bfloat16")
        return self.compare(list(zip(items, ctl)))[0]


#: the limit of each compared number: dwells are exact integers
LIMITS = {"pixels_differing": 0.0}
