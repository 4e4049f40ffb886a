"""Choose the Mariani-Silver crops of a traffic file.

Three steps, each printing or writing what the next one reads:

    # crops whose depth-0 border is mixed at dwell 4096 (they stay mixed
    # at any higher dwell): a JSON list of [cx, cy]
    python bench/tools/ms_catalog.py borders --out mixed.json
    # their whole dwell maps at the highest dwell a cell uses (chip)
    python bench/tools/ms_catalog.py dwells --mixed mixed.json \
        --max-dwell 1048576 --out dwells.npz
    # per crop and cell dwell: tasks, and the device iterations that a
    # tile-wide early exit leaves (the max dwell of each kernel call)
    python bench/tools/ms_catalog.py stats --dwells dwells.npz \
        --max-dwell 4096 --out stats.json

The dwell map at a lower dwell is the clamp of the map at a higher one,
so one ``dwells`` pass serves every cell.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import reference  # noqa: E402

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                     "ms-plane4096-sd64.json").read_text())
PLANE = {k: CONFIG[k] for k in ("x0", "y0", "x1", "y1", "width", "height")}
SIZE = CONFIG["width"] // CONFIG["initial_subdivision"]


def borders(a) -> None:
    import jax.numpy as jnp
    grid = PLANE["width"] // SIZE
    cells = [(cx, cy) for cy in range(grid) for cx in range(grid)]
    re, im = [], []
    for cx, cy in cells:
        c = reference.crop_bounds(PLANE, cx, cy, SIZE)
        xs, ys = reference.pixel_axes(c["x0"], c["y0"], c["x1"], c["y1"],
                                      SIZE, SIZE)
        bx = np.concatenate([xs, xs, np.full(SIZE - 2, xs[0]),
                             np.full(SIZE - 2, xs[-1])])
        by = np.concatenate([np.full(SIZE, ys[0]), np.full(SIZE, ys[-1]),
                             ys[1:-1], ys[1:-1]])
        re.append(bx.astype(np.float32))
        im.append(by.astype(np.float32))
    fn = reference.dwell_fn(a.max_dwell)
    out = []
    step = 256
    for s in range(0, len(cells), step):
        d = np.asarray(fn(jnp.asarray(np.stack(re[s:s + step])),
                          jnp.asarray(np.stack(im[s:s + step]))))
        out.extend(cells[s + i] for i in range(d.shape[0])
                   if not np.all(d[i] == d[i, 0]))
    Path(a.out).write_text(json.dumps([list(c) for c in out]))
    print(f"{len(out)} of {len(cells)} crops have a mixed border")


def dwells(a) -> None:
    mixed = json.loads(Path(a.mixed).read_text())
    maps = []
    step = a.batch
    for s in range(0, len(mixed), step):
        crops = [reference.crop_bounds(PLANE, cx, cy, SIZE)
                 for cx, cy in mixed[s:s + step]]
        maps.append(reference.crop_dwells(crops, a.max_dwell))
        print(f"{s + len(crops)} of {len(mixed)}", flush=True)
    np.savez_compressed(a.out, cells=np.asarray(mixed, np.int32),
                        dwell=np.concatenate(maps), max_dwell=a.max_dwell)


def stats(a) -> None:
    z = np.load(a.dwells)
    rows = []
    for (cx, cy), d in zip(z["cells"], z["dwell"]):
        d = np.minimum(d, a.max_dwell)
        _, tasks = reference.mariani_silver(d, CONFIG["max_depth"],
                                              CONFIG["split"])
        iters = sum(bmax + lmax for _, _, bmax, lmax in tasks)
        at_max = sum((bmax == a.max_dwell) + (lmax == a.max_dwell)
                     for _, _, bmax, lmax in tasks)
        rows.append({"cx": int(cx), "cy": int(cy), "tasks": len(tasks),
                     "leaves": sum(1 for t in tasks if t[1]),
                     "dwell_max": int(d.max()),
                     "calls_at_max": int(at_max), "iters": int(iters)})
    Path(a.out).write_text(json.dumps(rows))
    print(f"{len(rows)} crops at dwell {a.max_dwell}")


def main() -> None:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="step", required=True)
    b = sub.add_parser("borders")
    b.add_argument("--max-dwell", type=int, default=4096)
    b.add_argument("--out", required=True)
    d = sub.add_parser("dwells")
    d.add_argument("--mixed", required=True)
    d.add_argument("--max-dwell", type=int, required=True)
    d.add_argument("--batch", type=int, default=128)
    d.add_argument("--out", required=True)
    s = sub.add_parser("stats")
    s.add_argument("--dwells", required=True)
    s.add_argument("--max-dwell", type=int, required=True)
    s.add_argument("--out", required=True)
    a = ap.parse_args()
    {"borders": borders, "dwells": dwells, "stats": stats}[a.step](a)


if __name__ == "__main__":
    main()
