"""The wire readers (``fed.downlink_idle_ms``, ``fed.uplink_idle_ms``) on
hand-made events: device idle time inside the program's ``fed.broadcast``
and ``fed.collect`` spans, per traced round."""
import os

import pytest

import bench
import xtrace

DEV, HOST = "/device:TPU:0", "/host:CPU"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ev(plane, line, name, start, dur):
    return (plane, line, name, float(start), float(dur), "")


# Two rounds in a window [0, 1000) ns. The chip is busy in [0, 150),
# [250, 400), [450, 620) and [800, 1000): idle in [150, 250),
# [400, 450) and [620, 800).
DEVICE = [ev(DEV, xtrace.OPS_LINE, f"%op.{i} = x", s, d)
          for i, (s, d) in enumerate([(0, 150), (250, 150), (450, 170),
                                      (800, 200)])]
ROUNDS = [
    ev(HOST, "python3", "pb.window", 0, 1000),
    ev(HOST, "python3", "fed.broadcast", -50, 70),     # clipped at 0
    ev(HOST, "python3", "fed.broadcast", 100, 200),
    ev(HOST, "python3", "fed.downlink", 120, 150),     # nested part
    ev(HOST, "python3", "fed.collect", 350, 150),
    ev(HOST, "python3", "fed.broadcast", 600, 100),
    ev(HOST, "python3", "fed.collect", 750, 150),
]


def read(name, events, rounds=2):
    trace = None if events is None else \
        xtrace.Trace(events, window_span="pb.window")
    ctx = bench.LayerContext(cell=None, trace=trace,
                             counters={"rounds_traced": rounds})
    return bench.load_module(bench.reader_path(BENCH, name)).read(ctx)


@pytest.mark.parametrize("name,want_ns", [
    # [150, 250) inside [100, 300) + [620, 700) inside [600, 700)
    ("fed.downlink_idle_ms.sync", (100 + 80) / 2),
    # [400, 450) inside [350, 500) + [750, 800) inside [750, 900)
    ("fed.uplink_idle_ms.xdevice", (50 + 50) / 2),
])
def test_idle_ms_per_round_by_hand(name, want_ns):
    assert read(name, DEVICE + ROUNDS) == pytest.approx(want_ns / 1e6)


@pytest.mark.parametrize("name", ["fed.downlink_idle_ms.sync",
                                  "fed.uplink_idle_ms.sync"])
def test_none_without_a_trace_rounds_spans_or_device(name):
    assert read(name, None) is None
    assert read(name, DEVICE + ROUNDS, rounds=0) is None
    # a program that records no fed spans (only the benchmark's)
    assert read(name, DEVICE + ROUNDS[:1]) is None
    # a capture with no device plane (the CPU)
    assert read(name, ROUNDS) is None
