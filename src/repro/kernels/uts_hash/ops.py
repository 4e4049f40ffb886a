"""Public wrapper for the UTS SHA-1 kernel + tree-shape helpers.

The padded kernel dispatch itself — backend selection, power-of-two
bucket padding, jit-cache bounding — lives in the shared
``repro.kernels.dispatch`` registry; this module is the ``uts_hash``
registration plus the *semantics* the algorithm layer needs from a
digest:

* ``uts_child_digests``   — registered-kernel dispatch;
* ``random_u31``          — canonical UTS extracts a 31-bit uniform from
                            the first digest word;
* ``geometric_children``  — number of children: Geometric(mean b0) with a
                            depth cutoff (paper: b0=4, d in 14..18).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..dispatch import KernelOp, dispatch, register_kernel
from .kernel import DEFAULT_BLOCK_N, uts_hash_pallas
from .numpy_impl import geometric_thresholds
from .ref import uts_child_digests_ref

__all__ = [
    "uts_child_digests", "uts_child_digests_ref",
    "root_digest", "random_u31", "geometric_children",
]


def _pallas_body(parent, child_ix, *, block_n: int = DEFAULT_BLOCK_N,
                 interpret: bool = False):
    # operands arrive bucket-padded, so clamping the block to the padded
    # lane count is static inside the trace
    bn = min(block_n, parent.shape[1])
    return uts_hash_pallas(parent, child_ix.reshape(-1), block_n=bn,
                           interpret=interpret)


def _ref_body(parent, child_ix, *, block_n: int = DEFAULT_BLOCK_N):
    return uts_child_digests_ref(parent, child_ix)


register_kernel(KernelOp(
    name="uts_hash",
    pallas_body=_pallas_body,
    reference_body=_ref_body,
    # parent [5, N] and child_ix [N] share the elastic lane dim "n"
    arg_dims=(((1, "n"),), ((0, "n"),)),
    pad_values=(0, 0),
    out_dims=((1, "n"),),
    bucket_floor=128,
    cost_hint=lambda parent, child_ix: float(parent.shape[1]),
))


def uts_child_digests(parent: jax.Array, child_ix: jax.Array, *,
                      block_n: int = DEFAULT_BLOCK_N,
                      backend: str | None = None,
                      kept: int | None = None) -> jax.Array:
    """SHA1(parent || be32(ix)) for [5, N] parents, [N] indices.

    backend: "tpu-pallas" (compiled Mosaic, TPU), "interpret" (Pallas
    interpreter — used by the kernel test sweeps), "ref" (pure-jnp oracle
    — the fast path on CPU, bit-identical by test), or None = auto.
    kept: how many of the N digests the caller uses, where it padded
    the operands itself (``dispatch``'s ``uts_hash.kept`` counter).
    """
    if parent.shape[1] == 0:
        return jnp.zeros((5, 0), jnp.uint32)
    return dispatch("uts_hash", parent, child_ix, backend=backend,
                    kept=kept, block_n=block_n)


def root_digest(seed: int) -> jax.Array:
    """Root node state: SHA1(zero_digest || be32(seed)) — [5, 1] uint32.

    Canonical UTS seeds the root by hashing the seed into a zero state.
    """
    zero = jnp.zeros((5, 1), jnp.uint32)
    ix = jnp.array([seed], jnp.uint32)
    return uts_child_digests_ref(zero, ix)


def random_u31(digest: jax.Array) -> jax.Array:
    """31-bit uniform integer from a [5, N] digest batch -> [N] int32."""
    return (digest[0] >> 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("b0", "max_depth",
                                              "max_children"))
def geometric_children(digest: jax.Array, depth: jax.Array, *,
                       b0: float = 4.0, max_depth: int = 18,
                       max_children: int = 64) -> jax.Array:
    """Number of children per node, Geometric(mean=b0), 0 past cutoff.

    m = floor(log(u) / log(1 - p)) with p = 1/(1+b0) and u the digest's
    31-bit uniform mapped into (0, 1) gives a geometric variable on
    {0,1,...} with mean b0 (the UTS GEO shape function).  It is counted
    exactly, as the thresholds of ``geometric_thresholds`` that u31 does
    not exceed: integer comparisons, so the chip, XLA on the CPU and the
    numpy twin grow the same tree (a float32 ``log`` differs between them
    near integer boundaries).  ``max_children`` clamps the tail so
    frontier buffers stay bounded (P(m > 64) ~ (4/5)^64 ~ 6e-7 at b0=4).
    """
    t = jnp.asarray(geometric_thresholds(b0, max_children))
    u31 = random_u31(digest)
    m = jnp.sum(u31[:, None] <= t[None, :], axis=1, dtype=jnp.int32)
    return jnp.where(depth >= max_depth, 0, m)
