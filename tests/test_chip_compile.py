"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode accepts (layouts, relayouts,
VMEM use), so each kernel is compiled here at the widths a chip run
dispatches.  Nothing executes.  The topology is described inside a
module-scoped fixture, so only the test process given this file loads the
TPU library, and every test skips where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.algorithms.betweenness import _bc_batch_from_edges, bc_batch
from repro.kernels.dispatch import _jitted, get_kernel
from repro.kernels.uts_hash.ops import geometric_children


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_uts_hash_compiles_at_slice_width(one_chip):
    # the algorithm hashes in slices of 4 * min(chunk, 4096) lanes
    body = get_kernel("uts_hash").pallas_body
    compiled = jax.jit(lambda p, ix: body(p, ix, block_n=2048)).lower(
        _spec((5, 16384), jnp.uint32, one_chip),
        _spec((16384,), jnp.uint32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [(256, 256), (8, 4096), (8, 8)])
def test_mandelbrot_compiles(one_chip, shape):
    # (256, 256): one tile of a 4096^2 plane; (8, L): a border row at the
    # bucket floor; (8, 8): a corner.  The paper's 5e6 max dwell.
    body = get_kernel("mandelbrot").pallas_body
    compiled = jax.jit(lambda re, im: body(
        re, im, max_iter=5_000_000, block=(256, 256))).lower(
        _spec(shape, jnp.float32, one_chip),
        _spec(shape, jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_geometric_children_compiles(one_chip):
    geometric_children.lower(_spec((5, 8192), jnp.uint32, one_chip),
                             _spec((8192,), jnp.int32, one_chip),
                             b0=4.0, max_depth=18).compile()


def test_bc_batch_compiles_without_bf16(one_chip):
    # N=4096 (R-MAT scale 12), S=32 sources per task (128 tasks)
    compiled = bc_batch.lower(_spec((4096, 4096), jnp.float32, one_chip),
                              _spec((32,), jnp.int32, one_chip)).compile()
    # path counts sigma must not pass through a one-pass bf16 product:
    # no array of the program is bf16
    assert "bf16[" not in compiled.as_text()


def test_bc_task_program_compiles_without_bf16(one_chip):
    # a regenerating task's one program: 8 * 4096 edge samples scattered
    # into the adjacency, then the sweeps of its 32 sources
    compiled = _bc_batch_from_edges.lower(
        _spec((2, 8 * 4096), jnp.int32, one_chip),
        _spec((32,), jnp.int32, one_chip), n=4096).compile()
    assert "bf16[" not in compiled.as_text()
    # the adjacency and the sweeps' state, far below one chip's 16 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 2**28


@pytest.mark.parametrize("op, shape, dtype, static", [
    ("uts_hash", ((5, 16384), (16384,)), jnp.uint32, ()),
    ("mandelbrot", ((8, 256), (8, 256)), jnp.float32,
     (("max_iter", 4096),))])
def test_dispatched_kernel_carries_its_name(one_chip, op, shape, dtype,
                                            static):
    # the jitted program and the kernel's instruction are named after the
    # op, so the profiler's host and device events say which kernel ran
    fn = _jitted(get_kernel(op), "tpu-pallas", static)
    lowered = fn.lower(*(_spec(s, dtype, one_chip) for s in shape))
    assert lowered.as_text().startswith(f"module @jit_{op} ")
    [line] = [ln for ln in lowered.compile().as_text().splitlines()
              if "tpu_custom_call" in ln]
    assert f"%{op}.1 = " in line
