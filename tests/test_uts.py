"""UTS correctness: hash oracle, determinism, parallel == sequential."""
import hashlib

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.algorithms.uts import (Bag, UTSParams, expand_bag,
                                  expected_tree_size, uts_parallel,
                                  uts_sequential, uts_spec)
from repro.core import ElasticExecutor, LocalExecutor, StagedController, \
    TaskShape, make_pool, run_irregular
from repro.kernels.uts_hash.numpy_impl import (geometric_children_np,
                                               uts_child_digests_np)

P6 = UTSParams(seed=19, b0=4.0, max_depth=6, chunk=2048)


@pytest.fixture(scope="module")
def seq_count_p6():
    return uts_sequential(P6)


def test_sha1_matches_hashlib():
    rng = np.random.RandomState(3)
    parents = rng.randint(0, 2**31, size=(5, 17)).astype(np.uint32)
    ixs = rng.randint(0, 10_000, size=(17,)).astype(np.uint32)
    got = uts_child_digests_np(parents, ixs)
    for j in range(17):
        msg = b"".join(int(parents[i, j]).to_bytes(4, "big")
                       for i in range(5)) + int(ixs[j]).to_bytes(4, "big")
        dig = hashlib.sha1(msg).digest()
        exp = [int.from_bytes(dig[4 * i:4 * i + 4], "big")
               for i in range(5)]
        assert [int(got[i, j]) for i in range(5)] == exp


def test_branching_mean_close_to_b0():
    rng = np.random.RandomState(0)
    # digests must be uniform over the FULL uint32 range (as SHA-1
    # words are) — the sampler reads the top 31 bits
    digests = rng.randint(0, 2**32, size=(5, 20000),
                          dtype=np.uint64).astype(np.uint32)
    depths = np.zeros(20000, np.int32)
    m = geometric_children_np(digests, depths, b0=4.0, max_depth=18)
    assert abs(float(m.mean()) - 4.0) < 0.15
    assert int(m.min()) >= 0


def test_geometric_children_jnp_matches_numpy():
    """The jnp child count (the chip's) equals the numpy twin (the host
    reference's) bit for bit: a one-ULP difference in the float32 log
    near an integer boundary would grow a different tree."""
    import jax.numpy as jnp
    from repro.kernels.uts_hash.ops import geometric_children
    rng = np.random.RandomState(11)
    n = 1 << 21
    digests = rng.randint(0, 2**32, size=(5, n),
                          dtype=np.uint64).astype(np.uint32)
    depths = rng.randint(0, 20, size=n).astype(np.int32)
    want = geometric_children_np(digests, depths, b0=4.0, max_depth=18)
    got = np.asarray(geometric_children(jnp.asarray(digests),
                                        jnp.asarray(depths),
                                        b0=4.0, max_depth=18))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def test_depth_cutoff_terminates():
    digests = np.random.RandomState(0).randint(
        0, 2**31, size=(5, 100)).astype(np.uint32)
    deep = np.full(100, 18, np.int32)
    assert geometric_children_np(digests, deep, max_depth=18).sum() == 0


def test_sequential_deterministic(seq_count_p6):
    assert uts_sequential(P6) == seq_count_p6


def test_different_seed_different_tree(seq_count_p6):
    assert uts_sequential(UTSParams(seed=20, b0=4.0, max_depth=6,
                                    chunk=2048)) != seq_count_p6


def test_tree_grows_with_depth():
    sizes = [uts_sequential(UTSParams(seed=19, b0=4.0, max_depth=d,
                                      chunk=2048)) for d in (3, 4, 5, 6)]
    assert sizes == sorted(sizes)
    assert sizes[-1] > sizes[0] * 10  # Table 1: exponential growth


def test_expected_size_formula():
    # sum_{l<=d} b0^l
    assert expected_tree_size(4.0, 2) == 21.0
    assert expected_tree_size(4.0, 18) == (4**19 - 1) / 3


def test_expand_bag_budget_and_leftover(seq_count_p6):
    count, leftover = expand_bag(Bag.root(P6), 100, P6)
    assert count <= 100
    assert leftover.size > 0
    # finishing the leftover yields the exact total
    count2, leftover2 = expand_bag(leftover, 2**60, P6)
    assert leftover2.size == 0
    assert count + count2 == seq_count_p6


@given(st.integers(2, 16), st.integers(50, 2000))
@settings(max_examples=8)
def test_parallel_count_invariant(split, iters, ):
    """Node count is invariant to (split_factor, iters) — the paper's
    correctness property for bag resizing."""
    p = UTSParams(seed=19, b0=4.0, max_depth=5, chunk=512)
    expected = uts_sequential(p)
    with LocalExecutor(3, invoke_overhead=0.0) as ex:
        res = uts_parallel(ex, p, shape=TaskShape(split, iters))
    assert res.count == expected


def test_parallel_on_elastic_executor(seq_count_p6):
    with ElasticExecutor(max_concurrency=8, invoke_overhead=0.0005,
                         invoke_rate_limit=None) as ex:
        res = uts_parallel(ex, P6, shape=TaskShape(8, 500))
    assert res.count == seq_count_p6
    assert res.tasks > 1
    assert res.peak_concurrency > 1


def test_parallel_with_staged_controller(seq_count_p6):
    ctrl = StagedController()
    with LocalExecutor(4, invoke_overhead=0.0) as ex:
        res = uts_parallel(ex, P6, shape=TaskShape(8, 300),
                           controller=ctrl)
    assert res.count == seq_count_p6


def test_bag_split_merge_roundtrip():
    _, bag = expand_bag(Bag.root(P6), 50, P6)
    parts = bag.split(4)
    assert sum(b.size for b in parts) == bag.size
    merged = Bag.merge(parts)
    assert merged.size == bag.size
    # digests preserved as a multiset (column order may differ)
    a = np.sort(bag.digests[0])
    b = np.sort(merged.digests[0])
    assert np.array_equal(a, b)


LOWERED_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def test_new_roots_lower_no_program():
    """Seeding a job is a host hash: neither a new root nor a second
    job on a new root lowers a program (each lowering was a serial
    stall before the job's first task)."""
    lowered = []

    def on_event(event, duration, **kw):
        if event == LOWERED_EVENT:
            lowered.append(event)

    params = [UTSParams(seed=s, b0=4.0, max_depth=5, chunk=512)
              for s in (3_000_000_019, 3_000_000_029, 3_000_000_031)]
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for p in params[:2]:
            assert Bag.root(p).size == 1
        assert lowered == []
        with make_pool("local", max_concurrency=2) as pool:
            first = run_irregular(pool, uts_spec(params[1]))
            second = run_irregular(pool, uts_spec(params[2]))
        assert lowered == []
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert first.output == uts_sequential(params[1])
    assert second.output == uts_sequential(params[2])
