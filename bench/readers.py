"""What the metric files under ``bench/metrics/`` share.

Each metric is a file ``bench/metrics/<name>.py`` with ``read(run)``,
which returns the metric's value or ``None`` where the run holds nothing
for it to read; the harness then leaves the metric out.  ``run`` is a
:class:`cell.Run`: the window's jobs, its length, the pool's task
records, the programs lowered inside it, and (traced runs) the reduced
device trace.
"""
from __future__ import annotations

import statistics
from typing import Optional


def work_per_s(run, unit: str) -> Optional[float]:
    """Work of the completed jobs over the window, where it is ``unit``."""
    if run.unit != unit or not run.jobs:
        return None
    return run.work / run.window_s


def tasks_per_s(run, kind: str) -> Optional[float]:
    if run.kind != kind or not run.jobs:
        return None
    return run.tasks / run.window_s


def task_ms(run, kind: str) -> Optional[float]:
    """Median start-to-complete of the window's tasks, from the pool's
    event log (real clock, one record per task)."""
    if run.kind != kind or not run.records:
        return None
    return 1e3 * statistics.median(r.end_time - r.start_time
                                   for r in run.records)


def compiles_in_window(run, kind: str) -> Optional[float]:
    """Programs JAX lowered inside the window: each is a compile or a
    load from the persistent cache, and either stalls a task."""
    if run.kind != kind:
        return None
    return float(run.lowered_in_window)


def kernel_ns_per_unit(run, kernel: str, unit: str) -> Optional[float]:
    """Device time of ``kernel``'s events over the work completed."""
    if run.trace is None or run.unit != unit or not run.work:
        return None
    ns = run.trace.kernel_ns(kernel)
    if not ns:
        return None
    return ns / run.work


def device_idle(run, kind: str) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, in percent."""
    if run.trace is None or run.kind != kind or not run.trace.window_ns:
        return None
    return 100.0 * (1.0 - run.trace.busy_ns / run.trace.window_ns)
