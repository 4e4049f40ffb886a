"""Choose the UTS root seeds of a traffic file: trees of one size.

    python bench/tools/uts_catalog.py --depth 11 --target 7.5e6 --band 0.02 \
        --roots 4000 --keep 32 --workers 4

A run of the benchmark draws its jobs from a fixed list of roots in an
order given by its seed, so every seed does the same work.  Tree sizes
at a fixed depth spread widely (Galton-Watson), so the list keeps only
roots whose tree lies within ``band`` of ``target`` nodes.  Candidates
are screened by their generations up to ``--screen`` (default depth - 2,
projected by b0 per level), and the kept ones are counted exactly.
Prints one JSON object: the roots with their exact node counts.
"""
from __future__ import annotations

import argparse
import json
import sys
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import reference  # noqa: E402


def _screen(args):
    root, b0, screen, depth = args
    gens = reference.uts_generations(root, b0, screen)
    tail = sum(b0 ** k for k in range(1, depth - screen + 1))
    return root, sum(gens) + gens[-1] * tail


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, required=True)
    ap.add_argument("--target", type=float, required=True)
    ap.add_argument("--band", type=float, default=0.02)
    ap.add_argument("--roots", type=int, default=4000)
    ap.add_argument("--keep", type=int, default=32)
    ap.add_argument("--b0", type=float, default=4.0)
    ap.add_argument("--screen", type=int, default=None)
    ap.add_argument("--workers", type=int, default=4)
    a = ap.parse_args()
    screen = min(a.depth, a.screen if a.screen is not None else a.depth - 2)
    lo, hi = a.target * (1 - a.band), a.target * (1 + a.band)
    with ProcessPoolExecutor(a.workers) as ex:
        est = list(ex.map(_screen, [(r, a.b0, screen, a.depth)
                                    for r in range(a.roots)], chunksize=16))
    # screen a little wider than the band: the projection is an estimate
    near = [r for r, n in est if lo * 0.99 <= n <= hi * 1.01]
    kept = []
    for r in near:
        n = reference.uts_count(r, a.b0, a.depth, threads=a.workers)
        if lo <= n <= hi:
            kept.append({"root_seed": r, "nodes": n})
        if len(kept) == a.keep:
            break
    print(json.dumps({"depth": a.depth, "target": a.target, "band": a.band,
                      "screened": a.roots, "near": len(near),
                      "roots": kept}))


if __name__ == "__main__":
    main()
