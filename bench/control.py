"""Readings that the limits of ``correct`` are set from, on the chip at a
cell's own size: for each seed, one run of the cell (its window at the
cell's load) with the program's readings, and the float8 control's
readings on the same sample.  One process reads every seed.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

Prints one JSON line per seed: the readings of both, and ``correct`` under
the cell's present limits.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    from bench import run as R

    cell = harness.find_cell(args.workload)
    try:
        devs = harness.check_devices(cell.chips)
    except harness.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 2
    harness.set_compile_cache(ROOT)
    peak = harness.peaks(devs[0].device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        res, _ = R.execute(cell, seed, args.seconds, False, devs,
                           t_start=time.perf_counter(), peak=peak,
                           control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "readings": res["control"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
