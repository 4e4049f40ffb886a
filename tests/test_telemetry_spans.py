"""The recorder of spans and counters inside the program
(``repro.core.telemetry``): off it records nothing; on it nests spans
within a thread, links a task's span to the span that submitted it,
sums counters exactly across threads, agrees with the profiler's host
trace, and counts what dispatch launched and what crossed to and from
the device."""
import itertools
import sys
import threading
import tracemalloc

import jax
import networkx as nx
import numpy as np
import pytest

from repro.algorithms import (MSParams, RMATParams, UTSParams, bc_spec,
                              ms_spec, rmat_graph, uts_spec)
from repro.algorithms import uts as uts_mod
from repro.core import LocalExecutor, TaskShape, WorkSpec, run_irregular
from repro.core import telemetry
from repro.kernels.dispatch import bucket, dispatch, to_device, to_host


@pytest.fixture(autouse=True)
def recorder_off():
    telemetry.disable()
    telemetry.collect()
    yield
    telemetry.disable()
    telemetry.collect()


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def test_off_records_nothing_and_allocates_nothing():
    first = telemetry.span("uts.frontier")
    assert telemetry.span("pool.task", task=3, parent=(1, 1)) is first
    assert telemetry.current() is None

    def sites(n):
        """Bytes the loop holds at its peak and after it, above where
        it began: the loop's own set-up is the same for every n."""
        tracemalloc.reset_peak()
        base, _ = tracemalloc.get_traced_memory()
        for _ in itertools.repeat(None, n):
            with telemetry.span("uts.frontier"):
                telemetry.count("h2d_bytes", 8)
                telemetry.current()
        now, peak = tracemalloc.get_traced_memory()
        return now - base, peak - base

    tracemalloc.start()
    try:
        sites(10)
        few, many = sites(10), sites(10_000)
    finally:
        tracemalloc.stop()
    assert few == many and few[0] == 0
    assert telemetry.collect() == ([], {})


def test_on_nests_parents_within_a_thread():
    telemetry.enable()
    with telemetry.span("master.job", spec="uts") as outer:
        with telemetry.span("master.seed"):
            with telemetry.span("uts.root"):
                pass
        with telemetry.span("master.wait"):
            pass
    with telemetry.span("master.job", spec="uts"):
        pass
    telemetry.disable()
    spans, counters = telemetry.collect()
    assert counters == {}
    by = _by_name(spans)
    job1, job2 = sorted(by["master.job"], key=lambda s: s.start)
    assert job1.span_id == outer.span_id and job1.parent is None
    assert job1.spec == "uts" and job2.parent is None
    assert job1.job != job2.job
    seed, = by["master.seed"]
    root, = by["uts.root"]
    wait, = by["master.wait"]
    assert seed.parent == job1.span_id and wait.parent == job1.span_id
    assert root.parent == seed.span_id
    assert {seed.job, root.job, wait.job} == {job1.job}
    for s in spans:
        assert s.start <= s.end
        assert s.thread == threading.current_thread().name
    assert job1.start <= seed.start <= root.start <= root.end <= seed.end
    assert seed.end <= wait.start <= wait.end <= job1.end


def _tiny_spec(depth: int = 3) -> WorkSpec:
    """A tree of tasks: each item ``d`` spawns two of ``d - 1``."""
    return WorkSpec(
        name="tree",
        execute=lambda d, shape: d,
        seed=lambda shape: [depth],
        split=lambda d, shape: [d - 1, d - 1] if d > 0 else [],
        reduce=lambda n, d: n + 1,
        init=lambda: 0)


def test_pool_task_parent_is_the_dispatch_that_submitted_it():
    telemetry.enable()
    with LocalExecutor(4) as pool:
        res = run_irregular(pool, _tiny_spec())
    telemetry.disable()
    spans, _ = telemetry.collect()
    assert res.output == 15 and res.tasks == 15
    by = _by_name(spans)
    job, = by["master.job"]
    assert job.spec == "tree"
    dispatches = {s.span_id: s for s in by["master.dispatch"]}
    tasks = by["pool.task"]
    assert len(tasks) == 15
    for t in tasks:
        d = dispatches[t.parent]
        assert d.parent == job.span_id
        assert t.thread != d.thread
        assert d.start <= t.start
    assert {s.job for s in spans} == {job.job}
    assert {s.name for s in spans} == {
        "master.job", "master.seed", "master.dispatch", "master.wait",
        "master.fold", "master.finish", "pool.task"}


def test_counters_exact_under_eight_threads():
    telemetry.enable()
    per_thread, threads = 2000, 8
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=lambda: [
            telemetry.count("h2d_bytes", 3) for _ in range(per_thread)])
            for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(old)
    telemetry.disable()
    _, counters = telemetry.collect()
    assert counters == {"h2d_bytes": 3 * per_thread * threads}


def _host_events(trace_dir):
    """Names of the events on the profiler's host plane."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    names = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            names += [e.name for line in plane.lines for e in line.events]
    return names


@pytest.mark.parametrize("job", ["uts", "ms", "bc"])
def test_spans_agree_with_the_profiler_host_trace(job, tmp_path,
                                                  monkeypatch):
    # the task body takes its chip branch (kernels on their reference
    # bodies), so every span site it has on the chip runs here
    monkeypatch.setattr(uts_mod, "on_tpu", lambda: True)
    if job == "uts":
        spec = uts_spec(UTSParams(seed=19, max_depth=4, chunk=64))
        shape = TaskShape(split_factor=4, iters=40)
    elif job == "bc":
        spec = bc_spec(RMATParams(scale=5, seed=2), n_tasks=4)
        shape = None
    else:
        spec = ms_spec(MSParams(width=32, height=32, max_dwell=32,
                                max_depth=2, initial_subdivision=2))
        shape = None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with LocalExecutor(4) as pool:
        n_events = len(pool.events)
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            telemetry.enable()
            res = run_irregular(pool, spec, shape=shape)
            telemetry.disable()
        records = {r.task_id: r for r in pool.events.tail(n_events).records}
    spans, counters = telemetry.collect()
    names = {s.name for s in spans}
    assert not any(n == "job" or n.startswith("task.") for n in names)
    phases = {"uts": {"uts.root", "uts.frontier", "uts.merge",
                      "dispatch.geometric_children", "dispatch.uts_hash"},
              "ms": {"ms.coords", "ms.classify", "dispatch.mandelbrot"},
              "bc": {"bc.graph", "bc.upload", "dispatch.bc_batch"}}[job]
    assert phases | {"device.pull", "pool.task", "master.job"} <= names
    assert counters["h2d_bytes"] > 0 and counters["d2h_bytes"] > 0
    host = _host_events(tmp_path)
    for name in names:
        assert host.count(name) == sum(s.name == name for s in spans), name
    tasks = [s for s in spans if s.name == "pool.task"]
    assert len(tasks) == res.tasks == len(records)
    for t in tasks:
        r = records[t.task]
        assert r.start_time <= t.start <= t.end <= r.end_time


def test_dispatch_counts_launched_and_kept():
    telemetry.enable()
    row = np.linspace(-2.0, 0.5, 37, dtype=np.float32)[None, :]
    dispatch("mandelbrot", to_device(row), to_device(np.zeros_like(row)),
             max_iter=8)
    telemetry.disable()
    spans, counters = telemetry.collect()
    assert counters["mandelbrot.launched"] == 8 * bucket(37, 8)
    assert counters["mandelbrot.kept"] == 37
    assert counters["h2d_bytes"] == 2 * 37 * 4
    assert [s.name for s in spans].count("dispatch.mandelbrot") == 1


def test_hand_sized_generation_counts_pad_and_bytes(monkeypatch):
    """One generation of n nodes: ``uts_hash`` launched on whole
    4·chunk slices of the children, and every array that crossed
    counted in bytes."""
    monkeypatch.setattr(uts_mod, "on_tpu", lambda: True)
    params = UTSParams(seed=7, max_depth=6, chunk=128)
    root = uts_mod.Bag.root(params)
    digests, depths = uts_mod._expand_generation(root.digests, root.depths,
                                                 params)
    kids_d, kids_p = uts_mod._expand_generation(digests, depths, params)
    n, total = depths.size, kids_p.size
    assert n > 0 and total > 0
    telemetry.enable()
    again_d, again_p = uts_mod._expand_generation(digests, depths, params)
    telemetry.disable()
    _, counters = telemetry.collect()
    np.testing.assert_array_equal(again_d, kids_d)
    np.testing.assert_array_equal(again_p, kids_p)
    nb, hb = bucket(n, 128), 4 * 128
    slices = -(-total // hb)
    assert counters["uts_hash.launched"] == slices * hb
    assert counters["uts_hash.kept"] == total
    # up: padded digests and depths, then each slice's parents and
    # indices; down: the child counts, then each slice's digests
    assert counters["h2d_bytes"] == 6 * 4 * nb + slices * 6 * 4 * hb
    assert counters["d2h_bytes"] == 4 * nb + slices * 5 * 4 * hb


def test_bc_tasks_record_their_phases_and_counts():
    """Each BC task: its edge draws, upload, sweeps and pull as spans
    inside its ``pool.task``; its sources, its BFS levels (levels 0 to
    its block's depth, the depth by networkx), its edge samples
    scattered on the device and their int32 (src, dst) uploaded as
    counters.  Off, the same job records nothing and gives the same
    answer."""
    p = RMATParams(scale=6, seed=2)
    n, tasks = p.n_vertices, 4
    spec = bc_spec(p, n_tasks=tasks)
    with LocalExecutor(4) as pool:
        off = run_irregular(pool, spec).output
        assert telemetry.collect() == ([], {})
        telemetry.enable()
        on = run_irregular(pool, spec).output
        telemetry.disable()
    spans, counters = telemetry.collect()
    np.testing.assert_array_equal(on, off)
    by = _by_name(spans)
    task_ids = {s.span_id for s in by["pool.task"]}
    for name in ("bc.graph", "bc.upload", "dispatch.bc_batch",
                 "device.pull"):
        assert len(by[name]) == tasks, name
        assert {s.parent for s in by[name]} <= task_ids, name
    g = nx.from_numpy_array(rmat_graph(p), create_using=nx.DiGraph)
    levels = [1 + max(max(nx.single_source_shortest_path_length(
        g, int(s)).values()) for s in block)
        for block in np.array_split(np.arange(n), tasks)]
    assert counters["bc.sources"] == n
    assert counters["bc.levels"] == sum(levels)
    block, m = n // tasks, p.edge_factor * n
    assert counters["bc.edges"] == tasks * m
    assert counters["h2d_bytes"] == tasks * (2 * m * 4 + block * 4)
    assert counters["d2h_bytes"] == tasks * (n + 1) * 4


def test_bc_job_at_another_graph_seed_lowers_no_program():
    """A task's programs depend on the scale, not on the graph: a job
    on another R-MAT seed of the same scale reuses every one."""
    lowered = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(event)

    with LocalExecutor(4) as pool:
        run_irregular(pool, bc_spec(RMATParams(scale=6, seed=2),
                                    n_tasks=4))
        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            run_irregular(pool, bc_spec(RMATParams(scale=6, seed=11),
                                        n_tasks=4))
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
    assert lowered == []


def test_to_device_and_to_host_change_nothing():
    x = np.arange(12, dtype=np.float64).reshape(3, 4)
    d = to_device(x, np.float32)
    assert d.dtype == np.float32 and d.shape == (3, 4)
    back = to_host(d)
    assert isinstance(back, np.ndarray) and back.dtype == np.float32
    np.testing.assert_array_equal(back, x.astype(np.float32))
    assert to_device(np.arange(3, dtype=np.int32)).dtype == np.int32
