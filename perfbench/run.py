"""Run one benchmark cell once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Reads ``BENCHMARK.json`` at the checkout's root, finds the cell's
configuration, traffic mix, driver and correctness limits by name
(``perfbench/bench.py``), builds everything from ``--seed`` on the
device, warms up the cell's own shapes (set-up), measures for
``--seconds`` with the profiler off (``--trace 0``: the cell's
end-to-end metrics) or captures a short steady stretch of the window
with ``jax.profiler`` (``--trace 1``: its per-layer metrics), checks what
the timed path produced against the configuration's plain reference,
and prints one JSON object as the last line of stdout. Any platform but
the TPU, or fewer chips than the cell asks for, exits non-zero before a
result is printed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = bench.load_cell(args.workload)
    info = bench.device_info(cell.chips)
    bench.enable_cache()
    driver = bench.driver_for(cell)
    result, checks = driver.run(cell, seed=args.seed, seconds=args.seconds,
                                trace=bool(args.trace), device=info,
                                t_start=T_START)
    bench.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
