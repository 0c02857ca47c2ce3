"""Readings from which a cell's correctness limits are set: the program
on many seeds, the control (the reference in the next precision down in
the program's place) and planted faults on a few. No measured window.

    python3 perfbench/control.py --workload <cell> --seeds 1 2 3 \
        --modes program control half_batch --out <file.jsonl>

One JSON line per (mode, seed) goes to ``--out`` and to stdout, with
the readings judged against the cell's limits as a run judges them
(``checks``, ``correct``): the program's should come out correct, the
control's and each fault's not.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--modes", nargs="+", default=["program"])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    info = bench.device_info(cell.chips)
    bench.enable_cache()
    driver = bench.driver_for(cell)
    with open(args.out, "a") as f:
        for mode in args.modes:
            for seed in args.seeds:
                t0 = time.perf_counter()
                try:
                    r = driver.check_readings(cell, seed, mode)
                    err = None
                except Exception as e:  # a control that crashes has failed
                    r, err = None, f"{type(e).__name__}: {e}"
                checks = bench.judge(r or {}, cell.limits["checks"])
                line = {"workload": cell.name, "mode": mode, "seed": seed,
                        "readings": r, "error": err,
                        "correct": all(c["ok"] for c in checks.values()),
                        "checks": checks, "device": info,
                        "seconds": time.perf_counter() - t0}
                print(json.dumps(line), flush=True)
                f.write(json.dumps(line) + "\n")
                f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
