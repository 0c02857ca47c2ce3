"""Tiny cells for the CPU tests: the real drivers, references and limits
on fixture configurations a few dozen wide."""
from __future__ import annotations

import io
import json
from contextlib import redirect_stdout

import bench
import fixtures

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
BIG_SEED = 2 ** 31 + 4321


def fed_cell(tmp, traffic="sync_mrpc", like="fed_sync_mrpc"):
    wl = {"name": "fed_tiny", "config": "encoder-tiny",
          "traffic": "tiny_" + traffic, "chips": 1, "why": "test"}
    root = fixtures.make_root(tmp, [(wl, fixtures.tiny_encoder(),
                                     fixtures.tiny_fed_traffic(traffic),
                                     fixtures.read(f"perfbench/limits/"
                                                   f"{like}.json"))],
                              like=like)
    return bench.load_cell("fed_tiny", root=root)


def serve_cell(tmp, traffic="rag", like="serve_rag", **mix):
    """``mix`` overrides keys of the tiny traffic mix."""
    wl = {"name": "serve_tiny", "config": "decoder-tiny",
          "traffic": "tiny_" + traffic, "chips": 1, "why": "test"}
    root = fixtures.make_root(tmp, [(wl, fixtures.tiny_decoder(),
                                     dict(fixtures.tiny_serve_traffic(
                                         traffic), **mix),
                                     fixtures.read(f"perfbench/limits/"
                                                   f"{like}.json"))],
                              like=like)
    return bench.load_cell("serve_tiny", root=root)


def run_line(cell, seconds=2.0, trace=False, **kw):
    """Drive one run of the cell and return its printed last line."""
    import time
    drv = bench.driver_for(cell)
    buf = io.StringIO()
    with redirect_stdout(buf):
        res, checks = drv.run(cell, seed=BIG_SEED, seconds=seconds,
                              trace=trace, device=CPU,
                              t_start=time.perf_counter(), **kw)
        bench.emit(res, checks)
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def assert_well_formed(line, cell, trace=False):
    assert list(line)[-1] == "checks"
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in line
    assert line["attempted"] > 0
    want = {m["name"] for m in (cell.metrics_layer if trace
                                else cell.metrics_e2e)}
    assert set(line["metrics"]) <= want
    if not trace:
        assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
