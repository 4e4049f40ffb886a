"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (the kernel body executes in Python on CPU)."""
import hashlib

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.mandelbrot.ops import mandelbrot, mandelbrot_rect
from repro.kernels.mandelbrot.ref import coords, mandelbrot_ref
from repro.kernels.uts_hash.numpy_impl import (root_digest,
                                               uts_child_digests_np)
from repro.kernels.uts_hash.ops import uts_child_digests
from repro.kernels.uts_hash.ref import uts_child_digests_ref


# -- mandelbrot ----------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 8), (16, 64), (33, 17), (1, 100)])
@pytest.mark.parametrize("max_iter", [1, 13, 64])
def test_mandelbrot_pallas_matches_ref(shape, max_iter):
    cre, cim = coords(-2.0, -1.5, 1.0, 1.5, *shape)
    ref = mandelbrot_ref(cre, cim, max_iter)
    pal = mandelbrot(cre, cim, max_iter, block=(16, 32),
                     backend="interpret")
    assert np.array_equal(np.asarray(ref), np.asarray(pal))


@pytest.mark.parametrize("block", [(8, 8), (8, 64), (32, 32)])
def test_mandelbrot_block_shape_invariance(block):
    cre, cim = coords(-1.5, -1.0, 0.5, 1.0, 24, 40)
    a = mandelbrot(cre, cim, 32, block=block, backend="interpret")
    b = mandelbrot_ref(cre, cim, 32)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_mandelbrot_known_points():
    # c=0 is in the set; c=1 escapes at iteration 3 (z:0,1,2,5...)
    img = mandelbrot(jnp.array([[0.0, 1.0]]), jnp.array([[0.0, 0.0]]),
                     50, backend="ref")
    assert int(img[0, 0]) == 50
    assert int(img[0, 1]) == 3


def test_mandelbrot_rect_shapes():
    img = mandelbrot_rect(-2, -1.5, 1, 1.5, 37, 53, 16)
    assert img.shape == (37, 53)
    assert img.dtype == jnp.int32
    assert int(img.max()) <= 16 and int(img.min()) >= 0


# -- uts_hash -------------------------------------------------------------------

def _hashlib_oracle(parents, ixs):
    n = parents.shape[1]
    out = np.zeros((5, n), np.uint32)
    for j in range(n):
        msg = b"".join(int(parents[i, j]).to_bytes(4, "big")
                       for i in range(5)) + int(ixs[j]).to_bytes(4, "big")
        dig = hashlib.sha1(msg).digest()
        for i in range(5):
            out[i, j] = int.from_bytes(dig[4 * i:4 * i + 4], "big")
    return out


@pytest.mark.parametrize("n", [1, 2, 127, 128, 200])
def test_uts_hash_backends_agree(n):
    rng = np.random.RandomState(n)
    parents = rng.randint(0, 2**31, size=(5, n)).astype(np.uint32)
    ixs = rng.randint(0, 2**16, size=(n,)).astype(np.uint32)
    oracle = _hashlib_oracle(parents, ixs)
    got_np = uts_child_digests_np(parents, ixs)
    assert np.array_equal(got_np, oracle)
    got_ref = np.asarray(uts_child_digests(
        jnp.asarray(parents), jnp.asarray(ixs), backend="ref"))
    assert np.array_equal(got_ref, oracle)
    got_pl = np.asarray(uts_child_digests(
        jnp.asarray(parents), jnp.asarray(ixs), backend="interpret",
        block_n=128))
    assert np.array_equal(got_pl, oracle)


@given(st.integers(0, 2**31 - 1), st.integers(0, 2**20))
@settings(max_examples=10)
def test_uts_hash_property_vs_hashlib(word0, ix):
    parents = np.array([[word0], [1], [2], [3], [4]], np.uint32)
    ixs = np.array([ix], np.uint32)
    assert np.array_equal(uts_child_digests_np(parents, ixs),
                          _hashlib_oracle(parents, ixs))


def test_root_digest_deterministic():
    a = root_digest(19)
    b = root_digest(19)
    c = root_digest(42)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


@pytest.mark.parametrize("seed", [0, 1, 19, 42, 2**31, 2**32 - 1])
def test_root_digest_matches_device_oracle(seed):
    """The host root is the zero state's child ``seed`` under the jnp
    oracle and the numpy twin, bit for bit, in Bag's [5, 1] layout."""
    got = root_digest(seed)
    assert got.shape == (5, 1) and got.dtype == np.uint32
    zero = np.zeros((5, 1), np.uint32)
    ix = np.array([seed], np.uint32)
    assert np.array_equal(got, np.asarray(uts_child_digests_ref(
        jnp.asarray(zero), jnp.asarray(ix))))
    assert np.array_equal(got, uts_child_digests_np(zero, ix))
    assert np.array_equal(got, _hashlib_oracle(zero, ix))


@pytest.mark.parametrize("seed", [-1, 2**32, 2**40])
def test_root_digest_rejects_seeds_outside_u32(seed):
    with pytest.raises(ValueError):
        root_digest(seed)


def test_uts_hash_block_invariance():
    rng = np.random.RandomState(1)
    parents = rng.randint(0, 2**31, size=(5, 300)).astype(np.uint32)
    ixs = np.arange(300, dtype=np.uint32)
    a = np.asarray(uts_child_digests(jnp.asarray(parents),
                                     jnp.asarray(ixs),
                                     backend="interpret", block_n=128))
    b = np.asarray(uts_child_digests(jnp.asarray(parents),
                                     jnp.asarray(ixs), backend="ref"))
    assert np.array_equal(a, b)
