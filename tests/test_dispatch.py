"""The shared kernel-dispatch registry: backend resolution, bucket
padding round-trips for all three registered ops, and the O(log)
recompilation bound the bucketing policy exists to enforce."""
import math
from pathlib import Path

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.dispatch import (COMPILE_CACHE_DIR, KernelOp, bucket,
                                    compile_log, dispatch,
                                    enable_compile_cache, estimate_cost,
                                    get_kernel,
                                    register_kernel, registered_kernels,
                                    reset_compile_log, resolve_backend)
from repro.kernels.flash_attention.ops import flash_attention_fused
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.mandelbrot.ops import mandelbrot
from repro.kernels.mandelbrot.ref import coords, mandelbrot_ref
from repro.kernels.uts_hash.ops import uts_child_digests
from repro.kernels.uts_hash.ref import uts_child_digests_ref


# -- registry ------------------------------------------------------------------

def test_all_three_kernels_registered():
    names = registered_kernels()
    assert {"uts_hash", "mandelbrot", "flash_attention_fwd"} <= set(names)


def test_get_kernel_unknown_raises():
    with pytest.raises(ValueError, match="unknown kernel"):
        get_kernel("does_not_exist")


def test_resolve_backend():
    assert resolve_backend("ref") == "ref"
    assert resolve_backend("interpret") == "interpret"
    assert resolve_backend("pallas") == "tpu-pallas"  # legacy alias
    assert resolve_backend("tpu-pallas") == "tpu-pallas"
    assert resolve_backend(None) in ("tpu-pallas", "ref")
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda")


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compile-cache directory after the test."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_from_environment(cache_dir_config, monkeypatch,
                                        tmp_path):
    # the environment names the directory: JAX reads it, code sets none
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = cache_dir_config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert cache_dir_config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path_in_checkout(cache_dir_config,
                                              monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(COMPILE_CACHE_DIR)
    assert cache_dir_config.jax_compilation_cache_dir == \
        str(COMPILE_CACHE_DIR)
    root = Path(__file__).resolve().parents[1]
    assert COMPILE_CACHE_DIR == root / ".jax_cache"
    # the same directory on every call, in every process
    assert enable_compile_cache() == str(COMPILE_CACHE_DIR)


def test_bucket_policy():
    assert bucket(0) == 128 and bucket(1) == 128 and bucket(128) == 128
    assert bucket(129) == 256 and bucket(1000) == 1024
    assert bucket(5, floor=8) == 8 and bucket(9, floor=8) == 16
    with pytest.raises(ValueError):
        bucket(4, floor=0)


def test_estimate_cost_uses_unpadded_operands():
    par = np.zeros((5, 37), np.uint32)
    assert estimate_cost("uts_hash", par, np.zeros(37, np.uint32)) == 37.0


def test_dim_mismatch_raises():
    par = jnp.zeros((5, 8), jnp.uint32)
    ix = jnp.zeros((9,), jnp.uint32)  # shared dim "n" disagrees
    with pytest.raises(ValueError, match="dim 'n'"):
        dispatch("uts_hash", par, ix, backend="ref")


# -- pad/unpad round-trips: all three registered kernels ------------------------

@pytest.mark.parametrize("n", [1, 2, 37, 127, 128, 129, 300])
def test_uts_hash_round_trip_exact(n):
    """dispatch pads to the bucket and slices back: bit-identical to the
    reference body applied to the unpadded operands."""
    rng = np.random.RandomState(n)
    par = rng.randint(0, 2**31, size=(5, n)).astype(np.uint32)
    ix = rng.randint(0, 2**16, size=(n,)).astype(np.uint32)
    want = np.asarray(uts_child_digests_ref(jnp.asarray(par),
                                            jnp.asarray(ix)))
    got = np.asarray(uts_child_digests(jnp.asarray(par),
                                       jnp.asarray(ix), backend="ref"))
    assert got.shape == (5, n)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("shape", [(1, 1), (7, 13), (33, 17), (8, 64)])
def test_mandelbrot_round_trip_exact(shape):
    cre, cim = coords(-2.0, -1.5, 1.0, 1.5, *shape)
    want = np.asarray(mandelbrot_ref(cre, cim, 24))
    got = np.asarray(mandelbrot(cre, cim, 24, backend="ref"))
    assert got.shape == shape
    assert np.array_equal(got, want)


def test_flash_attention_round_trip_exact():
    """No elastic axes declared: dispatch must pass shapes through
    untouched and match the reference body exactly."""
    rng = np.random.RandomState(3)
    b, s, hkv, g, d = 1, 16, 2, 2, 8
    q = jnp.asarray(rng.randn(b, s, hkv, g, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32))
    got = flash_attention_fused(q, k, v, backend="ref")
    assert got.shape == (b, s, hkv, g, d)
    q2 = jnp.moveaxis(q, 1, 3).reshape(b * hkv * g, s, d)
    k2 = jnp.moveaxis(k, 1, 2).reshape(b * hkv, s, d)
    v2 = jnp.moveaxis(v, 1, 2).reshape(b * hkv, s, d)
    want = flash_attention_ref(q2, k2, v2, causal=True, window=None)
    want = jnp.moveaxis(want.reshape(b, hkv, g, s, d), 3, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_interpret_backend_round_trip():
    """The padded Pallas path (interpreter) agrees with ref through the
    same dispatch entry point."""
    rng = np.random.RandomState(7)
    par = rng.randint(0, 2**31, size=(5, 200)).astype(np.uint32)
    ix = np.arange(200, dtype=np.uint32)
    a = np.asarray(uts_child_digests(jnp.asarray(par), jnp.asarray(ix),
                                     backend="interpret", block_n=128))
    b = np.asarray(uts_child_digests(jnp.asarray(par), jnp.asarray(ix),
                                     backend="ref"))
    assert np.array_equal(a, b)


# -- recompilation bounds -------------------------------------------------------

def _uts_frontier_sizes(max_depth: int):
    """Generation-by-generation frontier sizes of a real UTS run."""
    from repro.algorithms.uts import Bag, UTSParams, _expand_generation
    params = UTSParams(seed=19, b0=4.0, max_depth=max_depth, chunk=4096)
    bag = Bag.root(params)
    sizes = []
    while bag.size:
        sizes.append(bag.size)
        children, depths = _expand_generation(bag.digests, bag.depths,
                                              params)
        bag = Bag(children, depths)
    return sizes


def test_jit_cache_misses_log_bounded_over_uts_run():
    """The acceptance bound: frontier sizes vary every generation of a
    UTS run (irregular by construction), yet the shared bucketing
    policy keeps distinct jit signatures O(log max_frontier)."""
    sizes = _uts_frontier_sizes(max_depth=7)
    assert len(set(sizes)) > 5          # genuinely irregular input
    max_frontier = max(sizes)
    reset_compile_log("uts_hash")
    rng = np.random.RandomState(0)
    for n in sizes:
        par = rng.randint(0, 2**31, size=(5, n)).astype(np.uint32)
        ix = rng.randint(0, 64, size=(n,)).astype(np.uint32)
        uts_child_digests(jnp.asarray(par), jnp.asarray(ix),
                          backend="ref")
    entries = compile_log("uts_hash")["uts_hash"]
    # one entry per power-of-two bucket in [floor, bucket(max_frontier)]
    bound = int(math.log2(bucket(max_frontier) / 128)) + 1
    assert len(entries) <= bound
    assert len(entries) < len(set(sizes))


def test_mandelbrot_compile_log_bounded():
    reset_compile_log("mandelbrot")
    for h, w in [(3, 5), (4, 9), (7, 7), (8, 8), (13, 30), (16, 31)]:
        cre, cim = coords(-1.0, -1.0, 1.0, 1.0, h, w)
        mandelbrot(cre, cim, 8, backend="ref")
    entries = compile_log("mandelbrot")["mandelbrot"]
    # 6 distinct sizes collapse onto {8,16}x{8,16,32} buckets max
    assert len(entries) <= 4


# -- registering a new op -------------------------------------------------------

def test_register_new_kernel_and_dispatch():
    """The README recipe: one KernelOp + dispatch, padding owned by the
    registry."""
    seen_shapes = []

    def body(x, *, scale):
        seen_shapes.append(x.shape)
        return x * scale

    register_kernel(KernelOp(
        name="_test_double",
        pallas_body=lambda x, *, scale, interpret=False: x * scale,
        reference_body=body,
        arg_dims=(((0, "n"),),),
        pad_values=(0,),
        out_dims=((0, "n"),),
        bucket_floor=4,
        cost_hint=lambda x: float(x.shape[0]),
    ))
    out = dispatch("_test_double", jnp.arange(5.0), backend="ref",
                   scale=2.0)
    assert out.shape == (5,)
    np.testing.assert_allclose(np.asarray(out),
                               2.0 * np.arange(5.0))
    assert seen_shapes == [(8,)]        # padded to the next bucket
