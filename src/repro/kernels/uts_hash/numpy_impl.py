"""Numpy fast path for UTS node expansion on the host.

The algorithm layer (the executor *task bodies*) runs on whatever machine
hosts the worker — on a pod that is the TPU (Pallas kernel); in this
container it is a single CPU core, where vectorized numpy beats the XLA
CPU emulation of the kernel by ~2 orders of magnitude.  Bit-identical to
ref.py / kernel.py (asserted in the test suite), so backends are
interchangeable.  The root digest is one 24-byte message per job, so it
is hashed here with ``hashlib`` and never becomes a device program.
"""
from __future__ import annotations

import hashlib
import math
import operator

import numpy as np

__all__ = ["root_digest", "uts_child_digests_np", "geometric_children_np",
           "geometric_thresholds"]

_H0 = (0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476, 0xC3D2E1F0)
_K = (0x5A827999, 0x6ED9EBA1, 0x8F1BBCDC, 0xCA62C1D6)


def _rotl(x: np.ndarray, n: int) -> np.ndarray:
    n = n % 32
    return (x << np.uint32(n)) | (x >> np.uint32(32 - n))


def uts_child_digests_np(parent: np.ndarray, child_ix: np.ndarray) -> np.ndarray:
    """SHA1(parent || be32(ix)): [5, N] uint32 x [N] uint32 -> [5, N]."""
    old = np.seterr(over="ignore")  # uint32 wraparound is the semantics
    try:
        parent = parent.astype(np.uint32, copy=False)
        n = parent.shape[1]
        zero = np.zeros(n, np.uint32)
        w = [parent[i] for i in range(5)]
        w.append(child_ix.astype(np.uint32, copy=False))
        w.append(np.full(n, 0x80000000, np.uint32))
        w.extend([zero] * 8)
        w.append(np.full(n, 24 * 8, np.uint32))
        for i in range(16, 80):
            w.append(_rotl(w[i - 3] ^ w[i - 8] ^ w[i - 14] ^ w[i - 16], 1))
        a, b, c, d, e = (np.full(n, h, np.uint32) for h in _H0)
        for i in range(80):
            if i < 20:
                f = (b & c) | (~b & d)
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
            tmp = _rotl(a, 5) + f + e + np.uint32(k) + w[i]
            e, d, c, b, a = d, c, _rotl(b, 30), a, tmp
        return np.stack([
            a + np.uint32(_H0[0]),
            b + np.uint32(_H0[1]),
            c + np.uint32(_H0[2]),
            d + np.uint32(_H0[3]),
            e + np.uint32(_H0[4]),
        ])
    finally:
        np.seterr(**old)


def root_digest(seed: int) -> np.ndarray:
    """Root node state: SHA1(zero_digest || be32(seed)) -> [5, 1] uint32.

    Canonical UTS seeds the root by hashing the seed into a zero state.
    The words are big-endian, word-major: the layout of ``Bag.digests``.
    A seed outside [0, 2**32) has no be32 encoding and raises ValueError.
    """
    seed = operator.index(seed)
    if not 0 <= seed < 2**32:
        raise ValueError(f"UTS root seed {seed} is outside [0, 2**32)")
    dig = hashlib.sha1(bytes(20) + seed.to_bytes(4, "big")).digest()
    return np.frombuffer(dig, ">u4").astype(np.uint32).reshape(5, 1)


def geometric_thresholds(b0: float, max_children: int) -> np.ndarray:
    """u31 thresholds of the Geometric(mean b0) child count -> [K] int32.

    With u = (u31 + 1) / (2^31 + 1) and q = 1 - p = b0 / (1 + b0), the
    count m = floor(log(u) / log(q)) is at least k exactly when u <= q^k,
    that is when u31 <= floor(q^k (2^31 + 1)) - 1 =: t[k-1].  The table
    is built once in float64 on the host; the count itself is then
    integer comparisons only, which every backend evaluates the same.
    """
    q = b0 / (1.0 + b0)
    t = [math.floor(q ** k * 2147483649.0) - 1
         for k in range(1, max_children + 1)]
    return np.clip(np.asarray(t, np.int64), -1, 2**31 - 1).astype(np.int32)


def geometric_children_np(digest: np.ndarray, depth: np.ndarray, *,
                          b0: float = 4.0, max_depth: int = 18,
                          max_children: int = 64) -> np.ndarray:
    """Numpy twin of ops.geometric_children (same thresholds)."""
    u31 = (digest[0] >> np.uint32(1)).astype(np.int32)
    t = geometric_thresholds(b0, max_children)
    m = np.sum(u31[:, None] <= t[None, :], axis=1, dtype=np.int32)
    return np.where(depth >= max_depth, 0, m).astype(np.int32)
