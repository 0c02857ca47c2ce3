"""Milliseconds per traced round in which the chip is idle inside the
program's ``fed.collect`` span (``FedSession.collect_updates``: the
per-client slice, encode and decode of each ``ClientUpdate``, and the
restack of the cohort)."""
import os

import bench

_idle = bench.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "span_idle.py"))


def read(ctx):
    return _idle.idle_ms_per_round(ctx, "fed.collect")
