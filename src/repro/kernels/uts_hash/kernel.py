"""Pallas TPU kernel: batched SHA-1 child-digest generation (UTS hot loop).

The UTS inner loop is node expansion: for every frontier node, hash the
parent digest with each child index.  On CPUs this is a scalar SHA-1 per
node; the TPU adaptation turns it into a *lane-parallel* integer pipeline:
each of the N lanes carries one (parent, child_index) message through the
80-round compression on the VPU (uint32 adds, xors, rotates - all native
vector ops).  There is no MXU work here by design: the kernel's job is to
keep the VPU busy on wide batches, which is exactly what makes bag-based
expansion (paper Listing 2) efficient on TPU.

Layout
  parent   [5, N] uint32  (word-major so N is the 128-wide lane axis)
  child_ix [1, N] uint32
  out      [5, N] uint32

Blocking: grid over N in ``block_n`` columns; all 5 words of a column
block live in VMEM together (5 * block_n * 4 B + 80-round temporaries;
block_n = 2048 keeps the whole working set < 1 MB).

The 80 rounds are unrolled statically: SHA-1's data flow is a fixed
16-deep sliding window, so unrolling gives the Mosaic compiler a straight
dependency chain with no dynamic indexing (TPU-friendly; a rolling
w[i mod 16] buffer would need per-step dynamic slices on the sublane
axis, which lowers poorly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import _H0, _K, _rotl

DEFAULT_BLOCK_N = 2048


def _uts_hash_kernel(parent_ref, child_ref, out_ref):
    parent = parent_ref[...]
    child_ix = child_ref[0, :]
    n = parent.shape[1]
    zero = jnp.zeros((n,), jnp.uint32)

    # Message schedule, first 16 words (single padded block of a 24-byte
    # message: 5 digest words + child index + pad + length).
    w = [parent[i] for i in range(5)]
    w.append(child_ix)
    w.append(jnp.full((n,), 0x80000000, jnp.uint32))
    w.extend([zero] * 8)
    w.append(jnp.full((n,), 24 * 8, jnp.uint32))
    for i in range(16, 80):
        w.append(_rotl(w[i - 3] ^ w[i - 8] ^ w[i - 14] ^ w[i - 16], 1))

    a = jnp.full((n,), _H0[0], jnp.uint32)
    b = jnp.full((n,), _H0[1], jnp.uint32)
    c = jnp.full((n,), _H0[2], jnp.uint32)
    d = jnp.full((n,), _H0[3], jnp.uint32)
    e = jnp.full((n,), _H0[4], jnp.uint32)

    for i in range(80):
        if i < 20:
            f = (b & c) | (jnp.bitwise_not(b) & d)
            k = _K[0]
        elif i < 40:
            f = b ^ c ^ d
            k = _K[1]
        elif i < 60:
            f = (b & c) | (b & d) | (c & d)
            k = _K[2]
        else:
            f = b ^ c ^ d
            k = _K[3]
        tmp = _rotl(a, 5) + f + e + jnp.uint32(k) + w[i]
        e, d, c, b, a = d, c, _rotl(b, 30), a, tmp

    out_ref[...] = jnp.stack([
        a + jnp.uint32(_H0[0]),
        b + jnp.uint32(_H0[1]),
        c + jnp.uint32(_H0[2]),
        d + jnp.uint32(_H0[3]),
        e + jnp.uint32(_H0[4]),
    ])


def uts_hash_pallas(parent: jax.Array, child_ix: jax.Array, *,
                    block_n: int = DEFAULT_BLOCK_N,
                    interpret: bool = False) -> jax.Array:
    """Raw pallas_call over block-aligned [5, N] digests / [1, N] indices."""
    _, n = parent.shape
    bn = min(block_n, n)
    if n % bn:
        raise ValueError(f"N={n} not aligned to block_n={bn}")
    grid = (n // bn,)
    return pl.pallas_call(
        _uts_hash_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((5, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((5, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((5, n), jnp.uint32),
        interpret=interpret,
        name="uts_hash",
    )(parent.astype(jnp.uint32), child_ix.reshape(1, -1).astype(jnp.uint32))
