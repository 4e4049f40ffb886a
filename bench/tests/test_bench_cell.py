"""The harness end to end on the CPU at tiny sizes: it refuses to run
without a TPU; with the look for a chip skipped it runs a cell through
the program and its reference; and with the timed path broken
underneath, ``correct`` comes out false."""
import copy
import importlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import cell

BENCH = Path(cell.__file__).resolve().parent
ROOT = BENCH.parent
BENCHMARK = cell.load_benchmark()
#: each job kind at a size a test run holds
TINY = {"uts-geo-b4.d11": {"max_depth": 5},
        "ms-plane4096-sd64.dwell4k": {"max_dwell": 48}}


def _run_script(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/cell.py", "--workload", "uts-geo-b4.d11",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    proc = _run_script(ROOT)
    assert proc.returncode == 2
    assert "not a TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_script(tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.fixture(autouse=True)
def cpu_as_chip(monkeypatch):
    """Skip the look for a TPU, and the check that the kernels ran
    compiled for one: the rest of a run goes as on the chip."""
    import jax
    monkeypatch.setattr(cell, "check_chip", lambda chips: jax.devices()[0])
    monkeypatch.setattr(cell, "_kernels_off_chip", lambda: 0)


def _tiny(workload, seconds=0.3, trace=False):
    entry, config, mix = cell.find_cell(BENCHMARK, workload)
    mix = copy.deepcopy(mix)
    mix["job"].update(TINY[workload])
    return cell.run_cell(entry, config, mix,
                         cell.cell_metrics(BENCHMARK, workload, trace),
                         seed=2**31 + 99, seconds=seconds, trace=trace,
                         say=lambda s: None)


@pytest.mark.parametrize("workload", sorted(TINY))
def test_tiny_run_is_correct(workload):
    result = _tiny(workload)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    names = {m["name"] for m in cell.cell_metrics(BENCHMARK, workload,
                                                  False)}
    assert set(result["metrics"]) == names
    assert all(m["value"] > 0 for m in result["metrics"].values())
    json.dumps(result)


def test_tiny_traced_run_reports_per_layer_metrics(monkeypatch):
    # the CPU runs no Pallas kernel: stand in a kernel time for it
    import readers
    monkeypatch.setattr(readers, "kernel_ns_per_unit",
                        lambda run, kernel, unit: 1.0)
    result = _tiny("uts-geo-b4.d11", trace=True)
    assert result["correct"] is True
    names = {m["name"] for m in cell.cell_metrics(BENCHMARK,
                                                  "uts-geo-b4.d11", True)}
    assert set(result["metrics"]) == names
    assert {"tasks_per_s.uts", "task_ms.uts",
            "compiles_in_window.uts"} <= set(result["metrics"])
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    # on the CPU the trace holds no uts_hash kernel to time
    result = _tiny("uts-geo-b4.d11", trace=True)
    assert "uts_hash_ns_per_node" not in result["metrics"]
    assert result["checks"]["metrics_unread"] == {"value": 1.0,
                                                  "limit": 0.0}
    assert result["correct"] is False


def _uts_count_altered(monkeypatch):
    uts = importlib.import_module("repro.algorithms.uts")
    real = uts.expand_bag

    def expand_bag(bag, iters, params):
        count, left = real(bag, iters, params)
        return count + 1, left
    monkeypatch.setattr(uts, "expand_bag", expand_bag)


def _uts_half_the_frontier(monkeypatch):
    uts = importlib.import_module("repro.algorithms.uts")
    real = uts._expand_generation

    def expand_generation(digests, depths, params):
        children, child_depths = real(digests, depths, params)
        keep = (child_depths.size + 1) // 2
        return children[:, :keep], child_depths[:keep]
    monkeypatch.setattr(uts, "_expand_generation", expand_generation)


def _ms_dwell_altered(monkeypatch):
    ms = importlib.import_module("repro.algorithms.mariani_silver")
    real = ms.evaluate_rect

    def evaluate_rect(rect, p):
        res = real(rect, p)
        res.dwell_to_fill += 1
        if res.dwell_array is not None:
            res.dwell_array = res.dwell_array + 1
        return res
    monkeypatch.setattr(ms, "evaluate_rect", evaluate_rect)


def _ms_half_the_border(monkeypatch):
    ms = importlib.import_module("repro.algorithms.mariani_silver")
    real = ms._border_dwells

    def border_dwells(rect, p):
        d = real(rect, p)
        return d[: max(1, d.size // 2)]
    monkeypatch.setattr(ms, "_border_dwells", border_dwells)


@pytest.mark.parametrize("workload,fault", [
    ("uts-geo-b4.d11", _uts_count_altered),
    ("uts-geo-b4.d11", _uts_half_the_frontier),
    ("ms-plane4096-sd64.dwell4k", _ms_dwell_altered),
    ("ms-plane4096-sd64.dwell4k", _ms_half_the_border),
], ids=["uts-answer-altered", "uts-half-the-frontier",
        "ms-answer-altered", "ms-half-the-border"])
def test_fault_makes_the_run_incorrect(monkeypatch, workload, fault):
    fault(monkeypatch)
    result = _tiny(workload)
    assert result["correct"] is False
    assert result["failed"] >= 1
    over = [k for k, v in result["checks"].items() if v["value"] > v["limit"]]
    assert over in (["node_count_gap"], ["pixels_differing"])


def test_output_is_the_jobs_own_copy():
    _, config, mix = cell.find_cell(BENCHMARK, "ms-plane4096-sd64.dwell4k")
    kind = cell.load_module(BENCH / "jobs" / "ms.py")
    jobs = kind.Jobs({**config, **mix["job"]})

    class Result:
        output = {"image": np.zeros((2, 2), np.int32)}
    out = jobs.output(Result)
    Result.output["image"][0, 0] = 1
    assert out[0, 0] == 0
