"""Find the highest rate an open-loop serving cell sustains: one set-up,
then one window per offered rate, each drained before the next.

    python3 perfbench/sweep.py --workload serve_chat --rates 1 2 3 \
        --seconds 30 --seed 5

Prints one JSON line per rate: TTFT and inter-token percentiles, the
completed output tokens per second, and the requests still queued when
the window closed (a backlog that grows means the rate is above what the
engine sustains). The cell's ``rate`` is then set, once, below the knee.
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    bench.device_info(cell.chips)
    bench.enable_cache()
    drv = bench.driver_for(cell)
    sv = drv.Serve(cell, args.seed)
    sv.warm_up()
    for rate in args.rates:
        tr = dict(cell.traffic, rate=rate)
        arr, p, o, w = drv.requests(tr, args.seed, args.seconds,
                                    len(sv.names))
        sv.tracks, sv.live, sv.steps = [], [], []
        t0 = drv._open_loop(sv, arr, p, o, w, args.seconds, lambda now, submitting: None)
        backlog = len(sv.live)
        queued = sum(1 for t in sv.live if t.seen == 0)
        window = list(sv.tracks)
        t_drain = time.perf_counter()
        drv._drain(sv, t0)
        m = drv.window_metrics(sv, window, args.seconds)
        ttft = sorted((t.times[0] - t.due) for t in window if t.times)
        print(json.dumps({
            "rate": rate, "requests": len(window), "live_at_close": backlog,
            "queued_at_close": queued,
            "ttft_p50_ms": 1e3 * bench.percentile(ttft, 50),
            "drain_s": time.perf_counter() - t_drain,
            "preemptions": sv.preemptions(), **m}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
