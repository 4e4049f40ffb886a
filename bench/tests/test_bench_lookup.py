"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix, kernel and metric is found by its name."""
import json
import re
from pathlib import Path

import pytest

import cell
import traffic

BENCH = Path(cell.__file__).resolve().parent
BENCHMARK = cell.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LAYERS = ("master and pool", "task bodies", "dispatch", "kernels", "device")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


def test_top_level_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert BENCHMARK["command"] == ["python3", "bench/cell.py"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51


@pytest.mark.parametrize("name", CELLS)
def test_cell_finds_its_files(name):
    entry, config, mix = cell.find_cell(BENCHMARK, name)
    assert entry["chips"] == 1
    job = traffic.job_config(config, mix)
    assert set(mix["job"]) <= set(config["reduced"])
    assert (BENCH / "jobs" / f"{job['kind']}.py").is_file()
    for traced in (False, True):
        reported = cell.cell_metrics(BENCHMARK, name, traced)
        assert reported, (name, traced)
        for m in reported:
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    # setup_s and one more end-to-end metric in every cell
    e2e = {m["name"] for m in cell.cell_metrics(BENCHMARK, name, False)}
    assert "setup_s" in e2e and len(e2e) >= 2


def test_configs_match_their_entries():
    for c in BENCHMARK["configs"]:
        config = json.loads((BENCH.parent / c["file"]).read_text())
        assert config["name"] == c["name"]
        assert config["reduced"] == c["reduced"]
        assert set(c["reduced"]) <= set(config["source_values"])
        assert c["file"].startswith("bench/configs/")


def test_names_units_and_layers():
    names = [m["name"] for m in METRICS] + CELLS + \
        [c["name"] for c in BENCHMARK["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    texts = [e["why"] for e in BENCHMARK["workloads"] + BENCHMARK["configs"]]
    texts += [c["source"] for c in BENCHMARK["configs"]]
    texts += [m["layer"] for m in BENCHMARK["per_layer"]]
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        assert m["layer"] in LAYERS
        # every cell it lists reports the metric it moves
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))


@pytest.mark.parametrize("name", sorted(p.stem for p in
                                        (BENCH / "traffic").glob("*.json")))
def test_traffic_order_comes_from_the_seed(name):
    mix = traffic.load(BENCH / "traffic" / f"{name}.json")
    n = len(mix["items"])
    big = 2**31 + 12345

    def draw(seed):
        it = traffic.jobs(mix, seed)
        return [json.dumps(next(it), sort_keys=True) for _ in range(2 * n)]

    assert draw(big) == draw(big)
    # each pass over the catalogue is a permutation of it
    first = draw(big)
    assert sorted(first[:n]) == sorted(json.dumps(i, sort_keys=True)
                                       for i in mix["items"])
    assert draw(-7)[:n] != draw(big)[:n] or n == 1


def test_unlisted_traffic_key_is_refused():
    _, config, mix = cell.find_cell(BENCHMARK, CELLS[0])
    bad = dict(mix, job={**mix["job"], "b0": 2.0})
    with pytest.raises(ValueError, match="reduced"):
        traffic.job_config(config, bad)


def test_peaks_known_and_unknown():
    import peaks
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flop_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")


def test_uts_jobs_take_the_configured_task_shape():
    from repro.core import TaskShape
    _, config, mix = cell.find_cell(BENCHMARK, "uts-geo-b4.d11")
    kind = cell.load_module(BENCH / "jobs" / "uts.py")
    job = traffic.job_config(config, mix)
    spec = kind.Jobs(job).spec(mix["items"][0])
    assert spec.shape == TaskShape(split_factor=8, iters=50000)
    spec = kind.Jobs({**job, "split_factor": 2, "iters": 7}).spec(
        mix["items"][0])
    assert spec.shape == TaskShape(split_factor=2, iters=7)


def test_ms_jobs_render_one_rectangle():
    _, config, mix = cell.find_cell(BENCHMARK, "ms-plane4096-sd64.dwell4k")
    kind = cell.load_module(BENCH / "jobs" / "ms.py")
    job = traffic.job_config(config, mix)
    assert kind.Jobs(job).size == 64
    with pytest.raises(ValueError, match="one rectangle"):
        kind.Jobs({**job, "rects_per_job": 4})
