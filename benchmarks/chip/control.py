#!/usr/bin/env python3
"""Readings that set a training cell's limits, on the chip at the cell's
own size: the program's numbers over many seeds (the lower reading), and
the same numbers with the control or a fault in the program's place
(the upper reading).  The benchmark's own runs never run this.

  python3 benchmarks/chip/control.py --workload <cell> \
      --runs program:11,12,13 fp8:21,22,23 half_batch:31,32,33

Each run drives the cell's lane as ``run.py`` does, with a short window,
and prints one JSON line: the mode, the seed and the compared numbers.
The modes are the lane's (``fault_step`` in ``lanes/<lane>.py``):
``program`` is the program as the benchmark runs it, ``fp8`` the control
(the plain reference in the program's place, its matmuls in float8, the
precision below the configuration's), and the others are the faults the
cell can have.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", nargs="+", required=True,
                    metavar="MODE:SEED,SEED")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(REPO / "src"))
    import run as bench_run
    from cellspec import load_cell, load_plugin
    cell = load_cell(REPO / "BENCHMARK.json", args.workload)
    lane = load_plugin("lanes", cell.lane)
    for spec in args.runs:
        mode, seeds = spec.split(":")
        for seed in seeds.split(","):
            res = bench_run.run_cell(cell, int(seed), args.seconds, False,
                                     make_step=lane.fault_step(mode))
            if res is None:
                return 1
            print(json.dumps({"mode": mode, "seed": int(seed),
                              "correct": res["correct"],
                              "checks": {k: v["value"] for k, v
                                         in res["checks"].items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
