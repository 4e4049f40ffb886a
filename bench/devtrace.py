"""From a JAX profiler trace to the benchmark's device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  Device planes are named ``/device:TPU:<n>``
and their ``XLA Ops`` line holds one event per operation run; the host
plane ``/host:CPU`` holds the benchmark's spans (``job`` around each
job, ``task.<spec>`` around each task body) on the threads that ran
them, on the same clock.

* busy time: the union of the device's operation intervals inside the
  window, averaged over the devices that ran any;
* the window: from the first ``job`` span's start to the last one's end;
* kernel time: the summed durations of the operations that a kernel's
  file ``bench/kernels/<kernel>.json`` matches (its ``matches`` regular
  expression is found in the operation's HLO text, which spells out the
  kernel's output and operand shapes);
* idle gaps: the stretches inside the window in which no operation
  ran, each named by what the host was doing through it: the benchmark
  span that covers it (``task.<spec>``, else ``job``, else ``no job``)
  and the shortest other host event, on any thread, that covers it.
"""
from __future__ import annotations

import glob
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

KERNELS = Path(__file__).resolve().parent / "kernels"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
JOB_SPAN = "job"
TASK_PREFIX = "task."
#: entries of each list in ``breakdown``
TOP = 10

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Path:
    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return Path(found[0])


def kernel_matchers(names: Iterable[str]) -> Dict[str, "re.Pattern[str]"]:
    """Each kernel's ``matches`` expression, from its file."""
    return {n: re.compile(
        json.loads((KERNELS / f"{n}.json").read_text())["matches"])
        for n in names}


def union_ns(intervals: List[Interval]) -> Tuple[float, List[Interval]]:
    """Total length of the union of intervals, and the merged intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def _clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def op_label(name: str, kernels: Dict[str, "re.Pattern[str]"]) -> str:
    """A short name for an operation: its kernel's, or the HLO
    instruction's name without the ``%`` and the number."""
    for kernel, pattern in kernels.items():
        if pattern.search(name):
            return kernel
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


class Trace:
    """The numbers the benchmark reads from one trace."""

    def __init__(self, busy_ns: float, window_ns: float,
                 ops_ns: Dict[str, float], gaps_ns: Dict[str, float],
                 kernels: Iterable[str]) -> None:
        self.busy_ns = busy_ns
        self.window_ns = window_ns
        self.ops_ns = ops_ns
        self.gaps_ns = gaps_ns
        self._kernels = set(kernels)

    def kernel_ns(self, kernel: str) -> float:
        if kernel not in self._kernels:
            raise KeyError(f"kernel {kernel!r} was not matched")
        return self.ops_ns.get(kernel, 0.0)

    def breakdown(self) -> dict:
        def top(d: Dict[str, float]) -> list:
            return [[k, v / 1e9] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.ops_ns),
                "idle_gaps": top(self.gaps_ns)}


def reduce(path, kernel_names: Iterable[str] = ()) -> Trace:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)), kernel_names)


def reduce_profile(profile, kernel_names: Iterable[str] = ()) -> Trace:
    kernels = kernel_matchers(kernel_names)
    devices: List[List[Tuple[float, float, str]]] = []
    host: List[List[Tuple[float, float, str]]] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.append([(e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events])
    spans = [ev for line in host for ev in line
             if ev[2] == JOB_SPAN or ev[2].startswith(TASK_PREFIX)]
    jobs = [ev for ev in spans if ev[2] == JOB_SPAN]
    if jobs:
        lo, hi = min(s for s, _, _ in jobs), max(e for _, e, _ in jobs)
    elif devices:
        lo = min(s for ops in devices for s, _, _ in ops)
        hi = max(e for ops in devices for _, e, _ in ops)
    else:
        return Trace(0.0, 0.0, {}, {}, kernels)

    busy_total = 0.0
    ops_ns: Dict[str, float] = defaultdict(float)
    gaps_ns: Dict[str, float] = defaultdict(float)
    marks = [ev for line in host for ev in line
             if ev[2] != JOB_SPAN and not ev[2].startswith(TASK_PREFIX)]
    for ops in devices:
        clipped = []
        for s, e, name in ops:
            iv = _clip((s, e), lo, hi)
            if iv:
                clipped.append(iv)
                ops_ns[op_label(name, kernels)] += iv[1] - iv[0]
        busy, merged = union_ns(clipped)
        busy_total += busy
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for (s, e), label in zip(gaps, _host_labels(gaps, spans, marks)):
            gaps_ns[label] += e - s
    n = max(1, len(devices))
    return Trace(busy_total / n, hi - lo,
                 {k: v / n for k, v in ops_ns.items()},
                 {k: v / n for k, v in gaps_ns.items()}, kernels)


def _shortest_cover(gaps: List[Interval], events) -> List[Optional[str]]:
    """For each of the sorted, disjoint ``gaps``, the name of the
    shortest event that covers all of it, or None."""
    names: List[Optional[str]] = [None] * len(gaps)
    if not gaps or not events:
        return names
    starts = np.array([s for s, _ in gaps])
    ends = np.array([e for _, e in gaps])
    ev = sorted(events, key=lambda x: x[0] - x[1])   # longest first
    first = np.searchsorted(starts, [s for s, _, _ in ev], side="left")
    last = np.searchsorted(ends, [e for _, e, _ in ev], side="right")
    for i0, i1, (_, _, name) in zip(first, last, ev):
        if i1 > i0:
            names[i0:i1] = [name] * (i1 - i0)
    return names


def _host_labels(gaps: List[Interval], spans, marks) -> List[str]:
    """What the host was doing through each gap: the benchmark span
    that covers it (a task's, else the job's, else ``no job``) and the
    shortest other host event that covers it."""
    tasks = _shortest_cover(gaps, [x for x in spans if x[2] != JOB_SPAN])
    jobs = _shortest_cover(gaps, [x for x in spans if x[2] == JOB_SPAN])
    inner = _shortest_cover(gaps, marks)
    labels = []
    for task, job, mark in zip(tasks, jobs, inner):
        outer = task or job or "no job"
        labels.append(f"{outer} / {mark}" if mark else outer)
    return labels
