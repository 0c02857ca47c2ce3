"""The trace reduction on hand-made events and on a small trace recorded
on a TPU v5e (``data/v5e_fed_trace.json``: module and host-span events of
three HLoRA rounds)."""
import os

import pytest

import xtrace

DEV, HOST = "/device:TPU:0", "/host:CPU"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(plane, line, name, start, dur, module=""):
    return (plane, line, name, float(start), float(dur), module)


EVENTS = [
    ev(HOST, "python3", "pb.window", 0, 100),
    ev(HOST, "python3", "pb.step", 0, 60),
    ev(HOST, "python3", "serve.decode_step", 10, 20),
    ev(DEV, xtrace.MODULES_LINE, "jit_step(1)", 10, 20),
    ev(DEV, xtrace.OPS_LINE, "%fusion.1 = f32[8] fusion(...)", 10, 10),
    ev(DEV, xtrace.OPS_LINE, "%_kernel.2 = custom-call", 15, 10),
    ev(DEV, xtrace.MODULES_LINE, "jit_prefill(2)", 70, 20),
    ev(DEV, xtrace.OPS_LINE, "%_kernel.3 = custom-call", 70, 20),
    ev(DEV, xtrace.OPS_LINE, "%late = x", 95, 20),     # clipped at 100
]


def test_busy_is_the_union_of_operations():
    t = xtrace.Trace(EVENTS, window_span="pb.window")
    assert (t.t0, t.t1) == (0.0, 100.0)
    # [10, 25) + [70, 90) + [95, 100)
    assert t.busy_s() == pytest.approx(40e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_module_and_kernel_time():
    t = xtrace.Trace(EVENTS, window_span="pb.window")
    assert t.module_time(["jit_step"]) == (pytest.approx(20e-9), 1)
    assert t.op_time(["_kernel"]) == (pytest.approx(30e-9), 2)
    assert t.op_time(["_kernel"], modules=["jit_prefill"]) == \
        (pytest.approx(20e-9), 1)
    assert t.op_time(["_kernel"], modules=["jit_step"]) == \
        (pytest.approx(10e-9), 1)


def test_idle_gaps_go_to_the_innermost_host_span():
    t = xtrace.Trace(EVENTS, window_span="pb.window")
    assert t.idle_gaps() == [(0.0, 10.0), (25.0, 70.0), (90.0, 95.0)]
    got = dict(t.gap_attribution(["pb.", "serve."]))
    # [0,10) and [30,60) in pb.step; [25,30) in serve.decode_step, the
    # shorter span covering it; [60,70) and [90,95) only in pb.window
    assert got["pb.step"] == pytest.approx(40e-9)
    assert got["serve.decode_step"] == pytest.approx(5e-9)
    assert got["pb.window"] == pytest.approx(15e-9)


def test_breakdown_shape():
    t = xtrace.Trace(EVENTS, window_span="pb.window")
    b = t.breakdown(["pb."])
    names = [n for n, _ in b["device_ops"]]
    assert "%_kernel.3" in names and len(b["device_ops"]) <= 10
    assert all(isinstance(s, float) for _, s in b["idle_gaps"])


def test_spans_inside_the_window():
    t = xtrace.Trace(EVENTS, window_span="pb.window")
    assert t.span_durations("serve.decode_step") == [pytest.approx(20e-9)]


def test_recorded_v5e_trace():
    events = xtrace.load_events(os.path.join(DATA, "v5e_fed_trace.json"))
    t = xtrace.Trace(events, window_span="pb.window")
    secs, n = t.module_time(["local_train"])
    assert n == 3                         # one cohort step per round
    assert 0.3 < secs / n < 1.0           # seconds per round's trainer
    agg, m = t.module_time(["jit__unknown"])
    assert m == 3 and agg < secs
    rounds = [d for d in t.span_durations("pb.round")]
    assert rounds and sum(rounds) <= t.window_s * 1.0001
