"""Run one cell of the benchmark once, on the chip this process finds.

    python3 bench/cell.py --workload uts-geo-b4.d11 --seed 7 --seconds 51 \
        --trace 0

The cell, its configuration and its traffic are found by name:
``BENCHMARK.json`` names the cell's configuration file and traffic mix
(``bench/traffic/<traffic>.json``), the configuration's ``kind`` names
the job code (``bench/jobs/<kind>.py``), and every metric is read by
``bench/metrics/<metric>.py``.

A run: check that JAX's first device is a TPU and that there are as
many as the cell asks for (set-up starts here, once JAX holds the chip);
build one ``local`` pool; run the traffic's warm-up job (set-up ends
here); then run jobs back to back for
``--seconds``, each one ``run_irregular(pool, spec)``.  A job starts
only while less than ``--seconds`` has passed, and the last one started
runs to its end.  With ``--trace 1`` the window runs under the JAX
profiler and the per-layer metrics are read from its trace; with
``--trace 0`` the end-to-end metrics are printed.  Every job's output
is then compared with the plain reference (``bench/reference.py``), and
every metric the cell lists has to have been read.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its
limit.  The same numbers end standard error.  Without a TPU the run
exits with status 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import traffic as traffic_mod  # noqa: E402

#: kernels that must run compiled for the chip, and only so
CHIP_KERNELS = ("uts_hash", "mandelbrot")
#: a job still running after this many seconds is an error
JOB_TIMEOUT_S = 240.0
#: JAX's event for each program it lowers (compiled or loaded)
LOWERED_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer than the cell asks for."""


@dataclasses.dataclass
class Job:
    item: dict
    output: Any
    tasks: int
    t0: float
    t1: float


@dataclasses.dataclass
class Run:
    """What the metric files read."""

    kind: str
    #: what ``work`` counts: ``nodes``, ``px``
    unit: str
    jobs: List[Job]
    #: work of all jobs completed in the window
    work: float
    window_s: float
    setup_s: float
    #: the pool's task records of the window
    records: list
    lowered_in_window: int
    #: the reduced device trace (``devtrace.Trace``) of a traced run
    trace: Any = None

    @property
    def tasks(self) -> int:
        return sum(j.tasks for j in self.jobs)


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str, root: Path = ROOT):
    """The cell's entry, its configuration and its traffic, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"cell.py: no workload {workload!r} in "
                         f"BENCHMARK.json; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = traffic_mod.load(BENCH / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def cell_metrics(bench: dict, workload: str, traced: bool) -> List[dict]:
    """The metrics this cell reports: per-layer ones when traced, else
    end-to-end ones; a metric with ``workloads`` only in those cells."""
    out = []
    for m in bench["per_layer" if traced else "end_to_end"]:
        if "workloads" not in m or workload in m["workloads"]:
            out.append(m)
    return out


def check_chip(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform!r}, "
                     f"not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds "
                     f"{len(devices)}")
    return devices[0]


def _annotated(spec):
    """The spec with each task body inside a ``task.<name>`` host span."""
    import jax
    execute = spec.execute
    label = f"task.{spec.name}"

    def run(item, shape):
        with jax.profiler.TraceAnnotation(label):
            return execute(item, shape)

    return dataclasses.replace(spec, execute=run)


def _run_job(pool, jobs, item: dict) -> Job:
    import jax
    from repro.core import run_irregular
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("job"):
        res = run_irregular(pool, _annotated(jobs.spec(item)),
                            timeout=JOB_TIMEOUT_S)
    t1 = time.perf_counter()
    return Job(item, jobs.output(res), res.tasks, t0, t1)


def _kernels_off_chip() -> int:
    """Compile-log entries of the chip's kernels under another backend."""
    from repro.kernels.dispatch import compile_log
    log = compile_log()
    return sum(1 for op in CHIP_KERNELS for backend, _, _ in log.get(op, ())
               if backend != "tpu-pallas")


def run_cell(cell: dict, config: dict, traffic: dict, metrics: List[dict],
             seed: int, seconds: float, trace: bool,
             say: Callable[[str], None] = lambda s: print(
                 s, file=sys.stderr, flush=True)) -> dict:
    """One run of a cell; returns the result object."""
    import jax
    device = check_chip(int(cell["chips"]))
    # JAX's own start on the chip runs before any code of the program and
    # varies by seconds from process to process; set-up is timed from here
    t_ready = time.perf_counter()
    say(f"device {device.device_kind} found {t_ready - T_START} s after "
        f"start")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import make_pool
    from repro.kernels.dispatch import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, comes from the cache in
    # the runs after a cell's first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    kind = load_module(BENCH / "jobs" / f"{config['kind']}.py")
    jobs = kind.Jobs(traffic_mod.job_config(config, traffic))
    order = traffic_mod.jobs(traffic, seed)
    lowered = [0]
    counting = [False]

    def on_event(event: str, duration: float, **kw) -> None:
        if counting[0] and event == LOWERED_EVENT:
            lowered[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    done: List[Job] = []
    try:
        with make_pool(config["pool"]["kind"],
                       max_concurrency=int(config["pool"]["workers"])) \
                as pool:
            warm = _run_job(pool, jobs, traffic["warmup"])
            say(f"warmup {jobs.describe(warm.item, warm.output)} "
                f"tasks={warm.tasks} s={warm.t1 - warm.t0}")
            n_records = len(pool.events.records)
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            counting[0] = True
            t0 = time.perf_counter()
            setup_s = t0 - t_ready
            while time.perf_counter() - t0 < seconds:
                job = _run_job(pool, jobs, next(order))
                done.append(job)
                say(f"job {len(done)} {jobs.describe(job.item, job.output)} "
                    f"tasks={job.tasks} s={job.t1 - job.t0}")
            counting[0] = False
            window_s = done[-1].t1 - t0
            if trace:
                jax.profiler.stop_trace()
                say(f"trace written in {time.perf_counter() - done[-1].t1} s")
            records = pool.events.records[n_records:]
        stats = device.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        reduced = None
        if trace:
            import devtrace
            t_read = time.perf_counter()
            reduced = devtrace.reduce(devtrace.find_xplane(trace_dir),
                                      CHIP_KERNELS)
            say(f"trace read in {time.perf_counter() - t_read} s")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    off_chip = _kernels_off_chip()
    t_ref = time.perf_counter()
    compared, failed = jobs.compare([(j.item, j.output) for j in done])
    say(f"reference compared {len(done)} jobs in "
        f"{time.perf_counter() - t_ref} s")
    run = Run(kind=config["kind"], unit=kind.UNIT, jobs=done,
              work=sum(jobs.work(j.item, j.output) for j in done),
              window_s=window_s, setup_s=setup_s, records=records,
              lowered_in_window=lowered[0], trace=reduced)
    values: Dict[str, dict] = {}
    for m in metrics:
        v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    unread = [m["name"] for m in metrics if m["name"] not in values]
    if unread:
        say(f"metrics read nothing: {' '.join(unread)}")
    checks = {name: {"value": value, "limit": kind.LIMITS[name]}
              for name, value in compared.items()}
    checks["kernels_off_chip"] = {"value": float(off_chip), "limit": 0.0}
    # a metric the cell lists that reads nothing (a kernel the trace no
    # longer matches, say) fails the run rather than drop out unseen
    checks["metrics_unread"] = {"value": float(len(unread)), "limit": 0.0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result: Dict[str, Any] = {
        "correct": correct, "attempted": len(done), "failed": failed,
        "metrics": values,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": int(cell["chips"]), "memory_peak_bytes": peak}}
    if reduced is not None:
        result["device"]["busy_s"] = reduced.busy_ns / 1e9
        result["device"]["window_s"] = reduced.window_ns / 1e9
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name} value={c['value']} limit={c['limit']}")
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    bench = load_benchmark()
    cell, config, traffic = find_cell(bench, a.workload)
    try:
        result = run_cell(cell, config, traffic,
                          cell_metrics(bench, a.workload, bool(a.trace)),
                          a.seed, a.seconds, bool(a.trace))
    except NoChip as e:
        print(f"cell.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
