"""Plain references for the benchmark's jobs, independent of the program.

Nothing here imports the system under test.  The UTS counter is numpy
alone (SHA-1 and the Geometric child count, copied from the program's
numpy twin and from the UTS definition); the Mandelbrot dwell is a plain
``jax.numpy`` loop; the Mariani-Silver image follows the algorithm's
fill rule over that dwell map on the host.

Each reference takes a ``precision`` so that the same code, run one step
lower, is the control that the comparison must reject:

* UTS states its child count as integer comparisons against a float64
  threshold table; the control counts children from a float32 ``log``.
* Mariani-Silver states float32 coordinates and iteration; the control
  iterates in bfloat16.
"""
from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Tuple

import numpy as np

_H0 = (0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476, 0xC3D2E1F0)
_K = (0x5A827999, 0x6ED9EBA1, 0x8F1BBCDC, 0xCA62C1D6)
#: nodes per host thread in the UTS counter
_SLICE = 1 << 18
#: the Geometric child count is clamped here (UTS GEO with a bounded tail)
MAX_CHILDREN = 64
ESCAPE_RADIUS_SQ = 4.0


# -- UTS ----------------------------------------------------------------------

def _rotl(x: np.ndarray, n: int) -> np.ndarray:
    return (x << np.uint32(n)) | (x >> np.uint32(32 - n))


def sha1_child(parent: np.ndarray, child_ix: np.ndarray) -> np.ndarray:
    """SHA1(parent || be32(child_ix)): [5, N] uint32 x [N] -> [5, N]."""
    old = np.seterr(over="ignore")
    try:
        n = parent.shape[1]
        zero = np.zeros(n, np.uint32)
        w = [parent[i].astype(np.uint32) for i in range(5)]
        w.append(child_ix.astype(np.uint32))
        w.append(np.full(n, 0x80000000, np.uint32))
        w.extend([zero] * 8)
        w.append(np.full(n, 24 * 8, np.uint32))
        for i in range(16, 80):
            w.append(_rotl(w[i - 3] ^ w[i - 8] ^ w[i - 14] ^ w[i - 16], 1))
        a, b, c, d, e = (np.full(n, h, np.uint32) for h in _H0)
        for i in range(80):
            if i < 20:
                f, k = (b & c) | (~b & d), _K[0]
            elif i < 40:
                f, k = b ^ c ^ d, _K[1]
            elif i < 60:
                f, k = (b & c) | (b & d) | (c & d), _K[2]
            else:
                f, k = b ^ c ^ d, _K[3]
            tmp = _rotl(a, 5) + f + e + np.uint32(k) + w[i]
            e, d, c, b, a = d, c, _rotl(b, 30), a, tmp
        return np.stack([a + np.uint32(_H0[0]), b + np.uint32(_H0[1]),
                         c + np.uint32(_H0[2]), d + np.uint32(_H0[3]),
                         e + np.uint32(_H0[4])])
    finally:
        np.seterr(**old)


def root_digest(root_seed: int) -> np.ndarray:
    """The root node: SHA1(zero digest || be32(seed)) -> [5, 1]."""
    return sha1_child(np.zeros((5, 1), np.uint32),
                      np.array([root_seed], np.uint32))


def children_float64(digest: np.ndarray, b0: float) -> np.ndarray:
    """Geometric(mean b0) child counts as the configuration states them.

    With u = (u31 + 1) / (2^31 + 1) and q = b0 / (1 + b0), a node has at
    least k children exactly when u <= q^k: integer comparisons against
    a threshold table built in float64."""
    q = b0 / (1.0 + b0)
    t = np.array([math.floor(q ** k * 2147483649.0) - 1
                  for k in range(1, MAX_CHILDREN + 1)], np.int64)
    u31 = (digest[0] >> np.uint32(1)).astype(np.int64)
    return np.searchsorted(-t, -u31, side="right").astype(np.int64)


def children_float32(digest: np.ndarray, b0: float) -> np.ndarray:
    """The control: m = floor(log(u) / log(q)) in float32."""
    u31 = (digest[0] >> np.uint32(1)).astype(np.float32)
    u = (u31 + np.float32(1.0)) / np.float32(2147483649.0)
    q = np.float32(b0 / (1.0 + b0))
    m = np.floor(np.log(u) / np.log(q))
    return np.clip(m, 0, MAX_CHILDREN).astype(np.int64)


CHILD_COUNTS: Dict[str, Callable[[np.ndarray, float], np.ndarray]] = {
    "float64": children_float64, "float32": children_float32}


def uts_generations(root_seed: int, b0: float, max_depth: int,
                    threads: int = 1,
                    precision: str = "float64") -> List[int]:
    """Nodes of the UTS tree per depth, 0..max_depth.

    Level by level: a node at ``max_depth`` has no children, so the last
    generation is counted from its parents' child counts and never
    hashed."""
    children = CHILD_COUNTS[precision]
    frontier = root_digest(root_seed)
    sizes = [1]

    def slices(n: int):
        return [slice(s, min(s + _SLICE, n)) for s in range(0, n, _SLICE)]

    with ThreadPoolExecutor(max(1, threads)) as ex:
        for depth in range(max_depth):
            n = frontier.shape[1]
            counts = np.concatenate(list(ex.map(
                lambda s: children(frontier[:, s], b0), slices(n))))
            total = int(counts.sum())
            sizes.append(total)
            if total == 0:
                sizes.extend([0] * (max_depth - depth - 1))
                break
            if depth + 1 == max_depth:
                break
            parent_ix = np.repeat(np.arange(n), counts)
            first = np.cumsum(counts) - counts
            child_ix = (np.arange(total) - first[parent_ix]).astype(np.uint32)
            frontier = np.concatenate(list(ex.map(
                lambda s: sha1_child(frontier[:, parent_ix[s]], child_ix[s]),
                slices(total))), axis=1)
    return sizes


def uts_count(root_seed: int, b0: float, max_depth: int, threads: int = 1,
              precision: str = "float64") -> int:
    """Nodes of the UTS tree rooted at ``root_seed``, cut at ``max_depth``."""
    return sum(uts_generations(root_seed, b0, max_depth, threads, precision))


# -- Mandelbrot and Mariani-Silver --------------------------------------------

def pixel_axes(x0: float, y0: float, x1: float, y1: float, width: int,
               height: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pixel-centre coordinates of an image of the plane, in float64."""
    sx = (x1 - x0) / width
    sy = (y1 - y0) / height
    return (x0 + (np.arange(width) + 0.5) * sx,
            y0 + (np.arange(height) + 0.5) * sy)


def crop_bounds(plane: dict, cx: int, cy: int, size: int) -> dict:
    """The ``size`` x ``size`` crop at grid cell (cx, cy) of the plane,
    at the plane's own pixel pitch: an image of its own."""
    sx = (plane["x1"] - plane["x0"]) / plane["width"]
    sy = (plane["y1"] - plane["y0"]) / plane["height"]
    x0 = plane["x0"] + cx * size * sx
    y0 = plane["y0"] + cy * size * sy
    return {"x0": x0, "y0": y0, "x1": x0 + size * sx, "y1": y0 + size * sy,
            "width": size, "height": size}


def dwell_fn(max_dwell: int, precision: str = "float32",
             unroll: int = 8):
    """A jitted dwell map over [..., W] coordinates at ``max_dwell``.

    z <- z^2 + c from z = 0; the dwell is the number of iterations at
    which |z|^2 <= 4 still held, clamped at ``max_dwell``."""
    import jax
    import jax.numpy as jnp

    dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]

    def dwell(c_re, c_im):
        c_re = c_re.astype(dt)
        c_im = c_im.astype(dt)
        radius = jnp.asarray(ESCAPE_RADIUS_SQ, dt)
        two = jnp.asarray(2.0, dt)

        def body(_, carry):
            z_re, z_im, n = carry
            active = z_re * z_re + z_im * z_im <= radius
            new_re = z_re * z_re - z_im * z_im + c_re
            new_im = two * z_re * z_im + c_im
            return (jnp.where(active, new_re, z_re),
                    jnp.where(active, new_im, z_im),
                    n + active.astype(jnp.int32))

        z0 = jnp.zeros_like(c_re)
        n0 = jnp.zeros(c_re.shape, jnp.int32)
        return jax.lax.fori_loop(0, max_dwell, body, (z0, z0, n0),
                                 unroll=min(unroll, max_dwell))[2]

    return jax.jit(dwell)


def crop_dwells(crops: List[dict], max_dwell: int,
                precision: str = "float32") -> np.ndarray:
    """Dwell maps [n, h, w] of image crops, each a dict with ``x0, y0,
    x1, y1, width, height`` (all crops of one size), in one device call.
    """
    import jax.numpy as jnp

    re, im = [], []
    for c in crops:
        xs, ys = pixel_axes(c["x0"], c["y0"], c["x1"], c["y1"],
                            c["width"], c["height"])
        im_, re_ = np.meshgrid(ys, xs, indexing="ij")
        re.append(re_.astype(np.float32))
        im.append(im_.astype(np.float32))
    out = dwell_fn(max_dwell, precision)(jnp.asarray(np.stack(re)),
                                         jnp.asarray(np.stack(im)))
    return np.asarray(out)


def _split(rect: Tuple[int, int, int, int, int],
           split: int) -> List[Tuple[int, int, int, int, int]]:
    px0, py0, px1, py1, depth = rect
    xs = np.linspace(px0, px1, split + 1).astype(int)
    ys = np.linspace(py0, py1, split + 1).astype(int)
    return [(int(xs[j]), int(ys[i]), int(xs[j + 1]), int(ys[i + 1]),
             depth + 1)
            for i in range(split) for j in range(split)
            if xs[j + 1] > xs[j] and ys[i + 1] > ys[i]]


def _border(d: np.ndarray, rect) -> np.ndarray:
    px0, py0, px1, py1, _ = rect
    return np.concatenate([d[py0, px0:px1], d[py1 - 1, px0:px1],
                           d[py0 + 1:py1 - 1, px0],
                           d[py0 + 1:py1 - 1, px1 - 1]])


def mariani_silver(dwell: np.ndarray, max_depth: int, split: int,
                   initial_subdivision: int = 1):
    """The Mariani-Silver image over a per-pixel dwell map.

    A rectangle whose border pixels share one dwell is filled with it;
    one at ``max_depth``, or two pixels wide or high, keeps its pixels'
    own dwells; any other is split ``split`` x ``split``.  Returns the
    image and, per rectangle evaluated, ``(border_px, leaf_px,
    border_max_dwell, leaf_max_dwell)``."""
    h, w = dwell.shape
    image = np.zeros_like(dwell)
    sd = initial_subdivision
    xs = np.linspace(0, w, sd + 1).astype(int)
    ys = np.linspace(0, h, sd + 1).astype(int)
    stack = [(int(xs[j]), int(ys[i]), int(xs[j + 1]), int(ys[i + 1]), 0)
             for i in range(sd) for j in range(sd)]
    tasks = []
    while stack:
        rect = stack.pop()
        px0, py0, px1, py1, depth = rect
        border = _border(dwell, rect)
        if border.size and np.all(border == border[0]):
            image[py0:py1, px0:px1] = border[0]
            tasks.append((border.size, 0, int(border.max()), 0))
        elif depth >= max_depth or px1 - px0 <= 2 or py1 - py0 <= 2:
            block = dwell[py0:py1, px0:px1]
            image[py0:py1, px0:px1] = block
            tasks.append((border.size, block.size, int(border.max()),
                          int(block.max())))
        else:
            tasks.append((border.size, 0, int(border.max()), 0))
            stack.extend(_split(rect, split))
    return image, tasks
