"""The plain references agree with the program at small sizes, the
traffic catalogues hold what they claim, and the controls (one step of
precision lower) are rejected by the comparison."""
import json
from pathlib import Path

import numpy as np
import pytest

import cell
import reference

BENCH = Path(cell.__file__).resolve().parent
BENCHMARK = cell.load_benchmark()


def _jobs(workload, **job):
    entry, config, mix = cell.find_cell(BENCHMARK, workload)
    kind = cell.load_module(BENCH / "jobs" / f"{config['kind']}.py")
    return kind, kind.Jobs({**config, **mix["job"], **job}), mix


@pytest.mark.parametrize("root,depth", [(19, 6), (30, 5), (5006, 6), (1, 7)])
def test_uts_count_matches_the_program(root, depth):
    from repro.algorithms import UTSParams, uts_sequential
    assert reference.uts_count(root, 4.0, depth) == uts_sequential(
        UTSParams(seed=root, b0=4.0, max_depth=depth))


def test_uts_d8_catalogue_counts():
    mix = json.loads((BENCH / "traffic" / "d8.json").read_text())
    for item in mix["items"][:8]:
        gens = reference.uts_generations(item["root_seed"], 4.0, 8)
        assert sum(gens) == item["nodes"]
        # a catalogue root never dies out early: it has nodes at depth 6
        assert gens[6] > 0


def test_uts_d11_catalogue_is_one_size():
    mix = json.loads((BENCH / "traffic" / "d11.json").read_text())
    sizes = [i["nodes"] for i in mix["items"]]
    assert max(sizes) / min(sizes) < 1.05
    item = mix["items"][0]
    assert reference.uts_count(item["root_seed"], 4.0, 11, threads=2) == \
        item["nodes"]


@pytest.mark.parametrize("name", ["dwell4k", "dwell1m"])
def test_ms_catalogue_crops_split(name):
    """Every crop of a mix has a mixed depth-0 border at dwell 4096, so
    Mariani-Silver splits it (it stays mixed at any higher dwell)."""
    _, jobs, mix = _jobs(f"ms-plane4096-sd64.{name}", max_dwell=4096)
    crops = [jobs.crop(i) for i in mix["items"][:6]]
    for d in reference.crop_dwells(crops, 4096):
        border = reference._border(d, (0, 0, d.shape[1], d.shape[0], 0))
        assert not np.all(border == border[0])


@pytest.mark.parametrize("cx,cy", [(13, 0), (21, 26), (40, 17)])
def test_ms_image_matches_the_program(cx, cy):
    from repro.core import make_pool, run_irregular
    _, jobs, _ = _jobs("ms-plane4096-sd64.dwell4k", max_dwell=96)
    item = {"crop": [cx, cy]}
    with make_pool("local", max_concurrency=2) as pool:
        res = run_irregular(pool, jobs.spec(item))
    compared, failed = jobs.compare([(item, jobs.output(res))])
    assert compared == {"pixels_differing": 0.0} and failed == 0


def test_ms_fill_rule():
    """A rectangle with a uniform border is filled, whatever is inside."""
    d = np.full((8, 8), 5, np.int32)
    d[3:5, 3:5] = 9
    image, tasks = reference.mariani_silver(d, max_depth=5, split=2)
    assert np.all(image == 5) and len(tasks) == 1
    d[0, 0] = 7
    image, tasks = reference.mariani_silver(d, max_depth=5, split=2)
    assert np.array_equal(image, d) and len(tasks) > 1


def test_uts_control_is_rejected():
    """The float32 child count departs from the float64 one on about
    one node in 2e6 (numpy on the host); of the d8 catalogue, the tree
    of root 1881 holds such a node.  A run's window covers the whole
    catalogue."""
    kind, jobs, _ = _jobs("uts-geo-b4.d11", max_depth=8)
    mix = json.loads((BENCH / "traffic" / "d8.json").read_text())
    items = [i for i in mix["items"] if i["root_seed"] in (1881, 30)]
    ctl = jobs.control(items)
    assert ctl["node_count_gap"] > kind.LIMITS["node_count_gap"]
    own = [(i, reference.uts_count(i["root_seed"], 4.0, 8)) for i in items]
    assert jobs.compare(own)[0]["node_count_gap"] == 0.0


def test_ms_control_is_rejected():
    kind, jobs, mix = _jobs("ms-plane4096-sd64.dwell4k", max_dwell=256)
    ctl = jobs.control(mix["items"][:2])
    assert ctl["pixels_differing"] > kind.LIMITS["pixels_differing"]
