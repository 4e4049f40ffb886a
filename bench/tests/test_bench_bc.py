"""The betweenness-centrality cell on the CPU at tiny sizes: its
reference agrees with the program's graph and with networkx, a tiny run
is correct, a zeroed source block makes it incorrect, the bfloat16
control is rejected, and a traced run reports the cell's per-layer
metrics."""
import copy
import importlib
import json

import networkx as nx
import numpy as np
import pytest

import bc_reference
import cell

BENCH = cell.BENCH
BENCHMARK = cell.load_benchmark()
WORKLOAD = "bc-rmat-ssca2.s12"
PER_LAYER = {"tasks_per_s.bc", "task_ms.bc", "compiles_in_window.bc",
             "device_idle.bc", "bc_device_ns_per_source"}


@pytest.fixture(autouse=True)
def cpu_as_chip(monkeypatch):
    """Skip the look for a TPU, and the check that the kernels ran
    compiled for one: the rest of a run goes as on the chip."""
    import jax
    monkeypatch.setattr(cell, "check_chip", lambda chips: jax.devices()[0])
    monkeypatch.setattr(cell, "_kernels_off_chip", lambda: 0)


def _jobs(**job):
    _, config, mix = cell.find_cell(BENCHMARK, WORKLOAD)
    kind = cell.load_module(BENCH / "jobs" / f"{config['kind']}.py")
    return kind, kind.Jobs({**config, **mix["job"], **job}), mix


def _tiny(scale=6, seconds=0.3, trace=False):
    entry, config, mix = cell.find_cell(BENCHMARK, WORKLOAD)
    mix = copy.deepcopy(mix)
    mix["job"]["scale"] = scale
    return cell.run_cell(entry, config, mix,
                         cell.cell_metrics(BENCHMARK, WORKLOAD, trace),
                         seed=2**31 + 77, seconds=seconds, trace=trace,
                         say=lambda s: None)


@pytest.mark.parametrize("scale,seed", [(5, 2), (7, 3), (9, 2)])
def test_reference_graph_is_the_programs(scale, seed):
    from repro.algorithms import RMATParams, rmat_graph
    adj = rmat_graph(RMATParams(scale=scale, seed=seed))
    src, dst = bc_reference.rmat_edges(scale, 8, 0.55, 0.10, 0.10, seed)
    ours = np.zeros_like(adj)
    ours[src, dst] = 1.0
    assert np.array_equal(ours, adj)
    assert src.size == int(adj.sum())


@pytest.mark.parametrize("scale,seed", [(5, 2), (6, 7)])
def test_reference_matches_networkx(scale, seed):
    src, dst = bc_reference.rmat_edges(scale, 8, 0.55, 0.10, 0.10, seed)
    n = 1 << scale
    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(src.tolist(), dst.tolist()))
    d = nx.betweenness_centrality(g, normalized=False)
    expected = np.array([d[v] for v in range(n)])
    np.testing.assert_allclose(bc_reference.betweenness(src, dst, n),
                               expected, rtol=1e-12, atol=1e-9)


def test_tiny_run_is_correct():
    result = _tiny()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["checks"]["bc_max_rel_err"]["value"] <= 1e-5
    names = {m["name"] for m in cell.cell_metrics(BENCHMARK, WORKLOAD,
                                                  False)}
    assert names == {"job_s", "setup_s"} == set(result["metrics"])
    assert all(m["value"] > 0 for m in result["metrics"].values())
    json.dumps(result)


def test_a_zeroed_source_block_makes_the_run_incorrect(monkeypatch):
    bc = importlib.import_module("repro.algorithms.betweenness")
    real = bc._bc_task

    def bc_task(p, sources, adj):
        partial = real(p, sources, adj)
        return np.zeros_like(partial) if sources[0] == 0 else partial
    monkeypatch.setattr(bc, "_bc_task", bc_task)
    result = _tiny()
    assert result["correct"] is False
    assert result["failed"] >= 1
    over = [k for k, v in result["checks"].items() if v["value"] > v["limit"]]
    assert over == ["bc_max_rel_err"]


def test_control_exceeds_the_limit():
    # at scale 10 path counts pass 256, where bfloat16 rounds integers
    kind, jobs, mix = _jobs(scale=10)
    numbers = jobs.control(mix["items"])
    assert numbers["bc_max_rel_err"] > kind.LIMITS["bc_max_rel_err"]


def test_tiny_traced_run_reports_per_layer_metrics(monkeypatch):
    # the CPU trace holds no TPU operation: stand in a busy time for it
    import devtrace
    real = devtrace.reduce

    def reduce(path, kernel_names=()):
        trace = real(path, kernel_names)
        trace.busy_ns = trace.busy_ns or 1.0
        return trace
    monkeypatch.setattr(devtrace, "reduce", reduce)
    result = _tiny(trace=True)
    assert result["correct"] is True
    assert set(result["metrics"]) == PER_LAYER == {
        m["name"] for m in cell.cell_metrics(BENCHMARK, WORKLOAD, True)}
    assert result["metrics"]["compiles_in_window.bc"]["value"] == 0
    assert result["metrics"]["bc_device_ns_per_source"]["value"] > 0
    assert result["device"]["window_s"] > 0
