"""Mariani-Silver Mandelbrot rendering on the elastic executor (§4.1.2).

Recursive adjacency optimization: evaluate only the border of each
rectangle; if every border pixel has the same dwell, fill the rectangle
with it (valid because the Mandelbrot set — and each dwell band — has a
connected complement); otherwise split and recurse, with full per-pixel
evaluation at the maximum depth.  Nested parallelism: each split spawns
child tasks — since the unified-pool redesign this is the ``split`` hook
of ``ms_spec`` driven by the generic ``repro.core.run_irregular`` loop
(``mariani_silver`` remains as a shim over it).

Task bodies call the Pallas escape-time kernel (repro.kernels.mandelbrot)
for both border strips and leaf rectangles.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core import Pool, TaskShape, WorkSpec, run_irregular
from ..core.telemetry import span
from ..kernels.dispatch import to_device, to_host
from ..kernels.mandelbrot.ops import mandelbrot
from ..kernels.mandelbrot.ref import coords, mandelbrot_ref

__all__ = ["MSParams", "Rect", "Action", "RectResult", "ms_spec",
           "evaluate_rect", "evaluate_rects", "mariani_silver",
           "naive_render", "plane_coords", "MSResult"]


@dataclass(frozen=True)
class MSParams:
    width: int = 4096
    height: int = 4096
    max_dwell: int = 512          # paper runs 5M; tests use smaller
    x0: float = -2.0
    y0: float = -1.5
    x1: float = 1.0
    y1: float = 1.5
    split: int = 2                # each side halved -> 4 children
    max_depth: int = 5
    initial_subdivision: int = 4  # sd: initial grid of sd x sd rects


@dataclass(frozen=True)
class Rect:
    """Pixel-space rectangle [px0, px1) x [py0, py1) at a nesting depth."""
    px0: int
    py0: int
    px1: int
    py1: int
    depth: int

    @property
    def w(self) -> int:
        return self.px1 - self.px0

    @property
    def h(self) -> int:
        return self.py1 - self.py0


class Action(Enum):
    FILL = "fill"
    SET_DWELL_ARRAY = "set_dwell_array"
    SPLIT = "split"


@dataclass
class RectResult:
    rect: Rect
    action: Action
    dwell_to_fill: int = 0
    dwell_array: Optional[np.ndarray] = None


def _pixel_coords(rect: Rect, p: MSParams):
    """Complex-plane coordinates of the rect's pixel centers."""
    sx = (p.x1 - p.x0) / p.width
    sy = (p.y1 - p.y0) / p.height
    xs = p.x0 + (np.arange(rect.px0, rect.px1) + 0.5) * sx
    ys = p.y0 + (np.arange(rect.py0, rect.py1) + 0.5) * sy
    c_im, c_re = np.meshgrid(ys, xs, indexing="ij")
    return to_device(c_re, jnp.float32), to_device(c_im, jnp.float32)


def _border_coords(rect: Rect, p: MSParams):
    """Flattened coordinates of the rect's border pixels (1-D pair)."""
    c_re, c_im = _pixel_coords(rect, p)
    # Evaluate the 4 border strips as one [2, max(w,h)]-ish batch: cheaper
    # to just gather border coords into a single row vector.
    top = (c_re[0, :], c_im[0, :])
    bot = (c_re[-1, :], c_im[-1, :])
    left = (c_re[1:-1, 0], c_im[1:-1, 0])
    right = (c_re[1:-1, -1], c_im[1:-1, -1])
    bre = jnp.concatenate([top[0], bot[0], left[0], right[0]])
    bim = jnp.concatenate([top[1], bot[1], left[1], right[1]])
    return bre, bim


def _border_dwells(rect: Rect, p: MSParams) -> np.ndarray:
    """Dwells of the rectangle's border pixels (flattened)."""
    with span("ms.coords"):
        bre, bim = _border_coords(rect, p)
        bre, bim = bre[None, :], bim[None, :]
    return to_host(mandelbrot(bre, bim, p.max_dwell))[0]


def _classify(rect: Rect, border: np.ndarray,
              p: MSParams) -> RectResult:
    """FILL / SPLIT / leaf decision from the border dwells; leaf
    rectangles come back with ``dwell_array=None`` — the caller
    evaluates their interiors (singly or batched)."""
    if border.size and np.all(border == border[0]):
        return RectResult(rect, Action.FILL, dwell_to_fill=int(border[0]))
    if rect.depth >= p.max_depth or rect.w <= 2 or rect.h <= 2:
        return RectResult(rect, Action.SET_DWELL_ARRAY)
    return RectResult(rect, Action.SPLIT)


def evaluate_rect(rect: Rect, p: MSParams) -> RectResult:
    """Task body — paper Listing 3 (``Callable.call``)."""
    border = _border_dwells(rect, p)
    with span("ms.classify"):
        res = _classify(rect, border, p)
    if res.action is Action.SET_DWELL_ARRAY:
        with span("ms.coords"):
            c_re, c_im = _pixel_coords(rect, p)
        res.dwell_array = to_host(mandelbrot(c_re, c_im, p.max_dwell))
    return res


def evaluate_rects(rects: List[Rect], p: MSParams) -> List[RectResult]:
    """Fused task body: every border strip of the batch goes through ONE
    kernel dispatch (a single [1, sum(border lens)] row vector), then
    every leaf interior through one more (pixels flattened end to end).
    The dwell of each pixel is independent of its neighbours, so the
    per-rect results are bit-identical to :func:`evaluate_rect`."""
    if not rects:
        return []
    with span("ms.coords"):
        borders = [_border_coords(r, p) for r in rects]
        lens = [int(b[0].shape[0]) for b in borders]
        bre = jnp.concatenate([b[0] for b in borders])[None, :]
        bim = jnp.concatenate([b[1] for b in borders])[None, :]
    dwells = to_host(mandelbrot(bre, bim, p.max_dwell))[0]
    with span("ms.classify"):
        results: List[RectResult] = []
        off = 0
        for rect, n in zip(rects, lens):
            results.append(_classify(rect, dwells[off:off + n], p))
            off += n
        leaves = [r for r in results if r.action is Action.SET_DWELL_ARRAY]
    if leaves:
        with span("ms.coords"):
            flats = []
            for res in leaves:
                c_re, c_im = _pixel_coords(res.rect, p)
                flats.append((c_re.ravel(), c_im.ravel()))
            fre = jnp.concatenate([f[0] for f in flats])[None, :]
            fim = jnp.concatenate([f[1] for f in flats])[None, :]
        flat_dwell = to_host(mandelbrot(fre, fim, p.max_dwell))[0]
        with span("ms.assemble"):
            off = 0
            for res in leaves:
                r = res.rect
                res.dwell_array = \
                    flat_dwell[off:off + r.w * r.h].reshape(r.h, r.w)
                off += r.w * r.h
    return results


def _split_rect(rect: Rect, split: int) -> List[Rect]:
    xs = np.linspace(rect.px0, rect.px1, split + 1).astype(int)
    ys = np.linspace(rect.py0, rect.py1, split + 1).astype(int)
    out = []
    for i in range(split):
        for j in range(split):
            if xs[j + 1] > xs[j] and ys[i + 1] > ys[i]:
                out.append(Rect(xs[j], ys[i], xs[j + 1], ys[i + 1],
                                rect.depth + 1))
    return out


@dataclass
class MSResult:
    image: np.ndarray
    wall_time_s: float
    tasks: int
    filled_pixels: int
    evaluated_pixels: int

    @property
    def throughput(self) -> float:
        """Points (pixels) per second — paper's MP/s metric."""
        return self.image.size / self.wall_time_s if self.wall_time_s else 0.0


def ms_spec(p: MSParams) -> WorkSpec:
    """Mariani-Silver as a declarative ``WorkSpec``.

    Work items are pixel rectangles; the master folds FILL /
    SET_DWELL_ARRAY actions into the image and recurses on SPLIT via
    the ``split`` hook (Listing 3's nested parallelism)."""

    def seed(shape: TaskShape) -> List[Rect]:
        sd = p.initial_subdivision
        xs = np.linspace(0, p.width, sd + 1).astype(int)
        ys = np.linspace(0, p.height, sd + 1).astype(int)
        return [Rect(xs[j], ys[i], xs[j + 1], ys[i + 1], 0)
                for i in range(sd) for j in range(sd)]

    def execute(rect: Rect, shape: TaskShape) -> RectResult:
        return evaluate_rect(rect, p)

    def execute_batch(rects: List[Rect],
                      shape: TaskShape) -> List[RectResult]:
        return evaluate_rects(list(rects), p)

    def split(res: RectResult, shape: TaskShape) -> List[Rect]:
        if res.action is Action.SPLIT:
            return _split_rect(res.rect, p.split)
        return []

    def init() -> Dict[str, Any]:
        return {"image": np.zeros((p.height, p.width), np.int32),
                "filled": 0, "evaluated": 0}

    def reduce(state: Dict[str, Any], res: RectResult) -> Dict[str, Any]:
        r = res.rect
        if res.action is Action.FILL:
            state["image"][r.py0:r.py1, r.px0:r.px1] = res.dwell_to_fill
            state["filled"] += r.w * r.h
        elif res.action is Action.SET_DWELL_ARRAY:
            state["image"][r.py0:r.py1, r.px0:r.px1] = res.dwell_array
            state["evaluated"] += r.w * r.h
        return state

    def merge(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
        # every rectangle lands on exactly one shard and pixel writes
        # are disjoint, so shard images sum exactly (int32 on zeros) —
        # sharded renders are bit-identical to the single master
        return {"image": a["image"] + b["image"],
                "filled": a["filled"] + b["filled"],
                "evaluated": a["evaluated"] + b["evaluated"]}

    # WAL codecs (repro.chaos crash recovery): rects key on their 5
    # ints; results round-trip action + dwell payload exactly (dwells
    # are int arrays, so the JSON trip is lossless)
    def _enc_rect(r: Rect) -> list:
        # rect bounds may be numpy ints (np.linspace grids): canonical
        # keys need plain JSON ints
        return [int(r.px0), int(r.py0), int(r.px1), int(r.py1),
                int(r.depth)]

    def encode_result(res: RectResult) -> dict:
        enc: Dict[str, Any] = {"r": _enc_rect(res.rect),
                               "a": res.action.value}
        if res.action is Action.FILL:
            enc["f"] = int(res.dwell_to_fill)
        elif res.action is Action.SET_DWELL_ARRAY:
            enc["w"] = res.dwell_array.tolist()
            enc["dt"] = str(res.dwell_array.dtype)
        return enc

    def decode_result(enc: dict) -> RectResult:
        rect = Rect(*enc["r"])
        action = Action(enc["a"])
        arr = (np.asarray(enc["w"], np.dtype(enc["dt"]))
               if action is Action.SET_DWELL_ARRAY else None)
        return RectResult(rect, action,
                          dwell_to_fill=enc.get("f", 0),
                          dwell_array=arr)

    return WorkSpec(
        name="mariani_silver",
        execute=execute,
        execute_batch=execute_batch,
        seed=seed,
        split=split,
        reduce=reduce,
        init=init,
        merge=merge,
        cost_hint=lambda rect: float(rect.w * rect.h),
        encode_item=_enc_rect,
        encode_result=encode_result,
        decode_result=decode_result,
    )


def mariani_silver(executor: Pool, p: MSParams) -> MSResult:
    """Deprecated shim over ``run_irregular(pool, ms_spec(p))``."""
    warnings.warn(
        "mariani_silver is deprecated; use "
        "run_irregular(pool, ms_spec(p)) instead",
        DeprecationWarning, stacklevel=2)
    t0 = time.monotonic()
    r = run_irregular(executor, ms_spec(p))
    return MSResult(
        image=r.output["image"],
        wall_time_s=time.monotonic() - t0,
        tasks=r.tasks,
        filled_pixels=r.output["filled"],
        evaluated_pixels=r.output["evaluated"],
    )


_render_ref = jax.jit(mandelbrot_ref, static_argnums=2)


def plane_coords(p: MSParams):
    """Coordinates of every pixel center of the image, as [H, W] arrays."""
    return _pixel_coords(Rect(0, 0, p.width, p.height, 0), p)


def naive_render(p: MSParams) -> np.ndarray:
    """Escape-time over every pixel — the correctness oracle.

    Runs the pure-jnp reference, not the dispatched kernel, so on the
    chip it stays independent of the Pallas kernel under test."""
    return to_host(_render_ref(*plane_coords(p), p.max_dwell))
