#!/usr/bin/env python3
"""The control of a cell: the plain reference with the guarantee
``d <= eps`` broken at its edge (``reference.fit(..., ties=False)``) put
in the program's place, judged by the cell's own comparison.  It has to come
out not correct.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it builds the cell's table, clusters it with the control,
and prints the comparison's numbers beside their limits as one JSON
line.  No benchmark run calls this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import gen, main as bench_main, reference  # noqa: E402


def control_checks(cell: str, seed: int, n: int = 0):
    _, config, _, _, _ = bench_main.load_cell(cell)
    if n:
        config["data"]["n"] = n
    eps, min_pts = config["eps"], config["min_pts"]
    table = gen.make_table(config, seed)
    ref = reference.fit(table, eps, min_pts)
    ctl = reference.fit(table, eps, min_pts, ties=False)
    fit = reference.compare_fit(ref, ctl.labels(), ctl.core)
    return [("core_flags_differ", fit["core_flags"], 0),
            ("labels_not_dbscan", fit["labels"], 0)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        checks = control_checks(args.workload, seed)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": all(v <= lim for _, v, lim in checks),
            "checks": {k: {"value": v, "limit": lim}
                       for k, v, lim in checks},
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
