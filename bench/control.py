"""The control of a cell's comparison: the reference one step of
precision lower, put in the program's place, on the jobs a run draws.

    python3 bench/control.py --workload uts-geo-b4.d11 --jobs 9 \
        --seeds 11 12 13

For each seed it takes the first ``--jobs`` items a run with that seed
would run, computes the cell's compared numbers with the control in the
program's place (UTS: child counts from a float32 ``log``; Mariani-Silver:
dwells iterated in bfloat16), and prints each beside its limit.  Every
number should exceed its limit: the comparison rejects the control.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import cell  # noqa: E402
import traffic as traffic_mod  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--jobs", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    bench = cell.load_benchmark()
    entry, config, mix = cell.find_cell(bench, a.workload)
    kind = cell.load_module(BENCH / "jobs" / f"{config['kind']}.py")
    jobs = kind.Jobs(traffic_mod.job_config(config, mix))
    readings = {}
    for seed in a.seeds:
        items = list(itertools.islice(traffic_mod.jobs(mix, seed), a.jobs))
        numbers = jobs.control(items)
        readings[seed] = numbers
        for name, value in numbers.items():
            print(f"control {a.workload} seed={seed} jobs={a.jobs} "
                  f"{name}={value} limit={kind.LIMITS[name]}", flush=True)
    rejected = all(v > kind.LIMITS[n] for r in readings.values()
                   for n, v in r.items())
    print(json.dumps({"workload": a.workload, "rejected": rejected,
                      "readings": readings}))
    return 0 if rejected else 1


if __name__ == "__main__":
    sys.exit(main())
