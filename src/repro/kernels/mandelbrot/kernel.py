"""Pallas TPU kernel: Mandelbrot escape-time iteration.

TPU adaptation of the Mariani-Silver leaf compute.  The CUDA reference
uses dynamic parallelism (device-side child launches); TPUs have no such
mechanism, so the irregular recursion lives in the host-side master
(``repro.algorithms.mariani_silver``) and this kernel evaluates one dense
*tile* of the plane per grid step — the unit of work a "cloud function"
receives.

Tiling: the image is cut into (block_h, block_w) VMEM tiles, f32 in /
int32 out; three live buffers per tile (c_re, c_im, dwell) plus two z
registers' worth of temporaries, comfortably inside the ~16 MB VMEM
budget for 256x256 tiles (256*256*4 B = 256 KB per buffer).

The iteration loop is a ``while_loop`` with a vector convergence mask so
a tile whose points all escape early stops iterating (this is what makes
tile-level work irregular — interior tiles run to ``max_iter``, exterior
tiles exit in a few dozen iterations — and why the paper's elastic
executor fits this workload).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ESCAPE_RADIUS_SQ = 4.0
DEFAULT_BLOCK = (256, 256)


def _mandelbrot_kernel(c_re_ref, c_im_ref, dwell_ref, *, max_iter: int):
    c_re = c_re_ref[...]
    c_im = c_im_ref[...]
    z_re0 = jnp.zeros_like(c_re)
    z_im0 = jnp.zeros_like(c_im)
    # derived from the loaded tile, not a splat constant: Mosaic cannot
    # relayout a replicated int32 splat into the while_loop's carry
    dwell0 = (c_re * 0.0).astype(jnp.int32)

    def cond(carry):
        i, _, _, _, any_active = carry
        return jnp.logical_and(i < max_iter, any_active)

    def body(carry):
        i, z_re, z_im, dwell, _ = carry
        active = z_re * z_re + z_im * z_im <= ESCAPE_RADIUS_SQ
        new_re = z_re * z_re - z_im * z_im + c_re
        new_im = 2.0 * z_re * z_im + c_im
        z_re = jnp.where(active, new_re, z_re)
        z_im = jnp.where(active, new_im, z_im)
        dwell = dwell + active.astype(jnp.int32)
        return i + 1, z_re, z_im, dwell, jnp.any(active)

    _, _, _, dwell, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), z_re0, z_im0, dwell0, jnp.bool_(True)))
    dwell_ref[...] = dwell


def mandelbrot_pallas(
    c_re: jax.Array,
    c_im: jax.Array,
    max_iter: int,
    *,
    block: tuple = DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call over an already block-aligned (H, W) plane."""
    h, w = c_re.shape
    bh, bw = min(block[0], h), min(block[1], w)
    if h % bh or w % bw:
        raise ValueError(f"plane {h}x{w} not aligned to block {bh}x{bw}")
    grid = (h // bh, w // bw)
    spec = pl.BlockSpec((bh, bw), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_mandelbrot_kernel, max_iter=max_iter),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.int32),
        interpret=interpret,
    )(c_re.astype(jnp.float32), c_im.astype(jnp.float32))
