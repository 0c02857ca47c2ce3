"""Shared benchmark utilities."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np


def time_fn(fn, *args, warmup: int = 2, iters: int = 5, **kw) -> float:
    """Median wall-time per call in microseconds (blocks on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    """CSV row: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def obs_percentiles(metrics, name: str, scale: float = 1.0) -> dict:
    """``{'p50': ..., 'p99': ...}`` from a registry histogram (scaled),
    ``{}`` when nothing was observed — benches report latency from the
    same recorder/metrics the engines use, not their own timers."""
    h = metrics.histogram(name)
    if not h.count:
        return {}
    return {"p50": float(h.percentile(50)) * scale,
            "p99": float(h.percentile(99)) * scale}


def export_trace(recorder, prefix: str) -> dict:
    """Write ``<prefix>.trace.json`` (Chrome trace-event, perfetto-
    loadable) + ``<prefix>.events.jsonl`` from a recorder, validating
    the Chrome document on the way out."""
    from repro.obs import (validate_chrome_trace, write_chrome_trace,
                           write_jsonl)
    d = os.path.dirname(prefix)
    if d:
        os.makedirs(d, exist_ok=True)
    events = recorder.events()
    dropped = recorder.dropped
    trace_path = f"{prefix}.trace.json"
    jsonl_path = f"{prefix}.events.jsonl"
    doc = write_chrome_trace(events, trace_path, dropped=dropped)
    validate_chrome_trace(doc)
    n = write_jsonl(events, jsonl_path,
                    meta={"dropped": dropped} if dropped else None)
    return {"trace": trace_path, "jsonl": jsonl_path, "events": n,
            "dropped": dropped}


MESH_RESULT_TAG = "MESH_RESULT "


def run_mesh_child(module: str, quick: bool, devices: int = 8,
                   trace_path: str = None) -> dict:
    """Run ``python -m <module> --mesh-child`` in a subprocess with
    ``devices`` forced host devices and return its MESH_RESULT json.

    ``--xla_force_host_platform_device_count`` only takes effect before
    the first jax device query, and the benchmark parent has long since
    initialized jax on one device — so every mesh-scaling section
    measures in a child process, exactly like tests/test_mesh.py. The
    child prints one ``MESH_RESULT {...}`` line; everything else it says
    is passed through for the log.

    ``trace_path`` (optional) is exported to the child as the
    ``REPRO_CHILD_TRACE`` env var: children that support cross-process
    collection ``dump_stream`` their recorder there (JSONL + clock
    handshake) so the parent can ``merge_streams`` onto its timeline.

    The child runs on the CPU (``JAX_PLATFORMS=cpu``) whatever the
    parent runs on: its numbers are forced-host-device measurements by
    design, and on an accelerator host the parent already holds the
    device, so a child that asked for it would fail or hang."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}"
                        ).strip()
    if trace_path:
        env["REPRO_CHILD_TRACE"] = trace_path
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    cmd = [sys.executable, "-m", module, "--mesh-child"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1200)
    if proc.returncode != 0:
        tail = ((proc.stdout or "") + (proc.stderr or ""))[-2000:]
        raise RuntimeError(f"mesh child {module} failed:\n{tail}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith(MESH_RESULT_TAG):
            return json.loads(line[len(MESH_RESULT_TAG):])
    raise RuntimeError(f"mesh child {module} printed no "
                       f"{MESH_RESULT_TAG!r} line:\n{proc.stdout[-2000:]}")
