"""The one traffic generator: jobs drawn from a traffic file by seed.

A traffic file (``bench/traffic/<name>.json``) holds:

* ``kind``: the job kind it is written for (``uts``, ``ms``), which must
  match the configuration's;
* ``job``: the configuration keys this mix sets for every job (each one
  must be listed in the configuration's ``reduced``);
* ``warmup``: one job item run during set-up, cheap, that dispatches
  every shape the window will;
* ``items``: the catalogue of job items.  Every item of a mix does about
  the same work, so every seed does the same work in another order.

The window runs jobs back to back, closed loop: one job at a time, the
next one submitted when the last has finished.  ``jobs`` yields the
catalogue in an order drawn from the seed, reshuffled each time it is
used up.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterator

import numpy as np

#: seeds are any whole number; numpy's generator wants a non-negative one
_SEED_MASK = (1 << 64) - 1


def load(path: Path) -> dict:
    traffic = json.loads(Path(path).read_text())
    for key in ("kind", "job", "warmup", "items"):
        if key not in traffic:
            raise ValueError(f"{path}: traffic file has no {key!r}")
    if not traffic["items"]:
        raise ValueError(f"{path}: traffic file lists no items")
    return traffic


def job_config(config: dict, traffic: dict) -> dict:
    """The configuration as one job of this mix runs it."""
    if traffic["kind"] != config["kind"]:
        raise ValueError(f"traffic for {traffic['kind']!r} jobs given to a "
                         f"{config['kind']!r} configuration")
    unlisted = set(traffic["job"]) - set(config["reduced"])
    if unlisted:
        raise ValueError(f"traffic sets {sorted(unlisted)}, which the "
                         f"configuration does not list as reduced")
    return {**config, **traffic["job"]}


def rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(seed & _SEED_MASK)


def jobs(traffic: dict, seed: int) -> Iterator[dict]:
    """Job items in the seed's order, without end."""
    gen = rng(seed)
    items = traffic["items"]
    while True:
        for i in gen.permutation(len(items)):
            yield items[int(i)]
