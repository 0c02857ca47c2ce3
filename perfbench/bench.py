"""Shared harness pieces: the benchmark spec, cell lookup, seeds, the
device check, the compile cache, the per-layer metric readers and the
result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

    workload -> config entry -> ``file`` (sizes + ``reference`` module)
    workload.traffic -> ``perfbench/traffic/<traffic>.json`` (``driver``)
    driver -> ``perfbench/drivers/<driver>.py`` (``run(cell)``)
    workload name -> ``perfbench/limits/<workload>.json`` (correctness)
    per-layer metric -> ``perfbench/metrics/<name>.py`` (``read(ctx)``),
        or the file of its base name (``fed.train_ms.sync`` falls back
        to ``fed.train_ms.py``)

Nothing here touches JAX at import time.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(path: str):
    """Import a Python file by path (file names may hold '-' and '.')."""
    name = "pb_" + os.path.basename(path).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    root: str
    spec: dict
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    metrics_e2e: List[dict]
    metrics_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def path(self, rel: str) -> str:
        return os.path.join(self.root, rel)

    def reference(self):
        """The configuration's plain reference, beside its file."""
        return load_module(self.path(self.config["reference"]))


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r}; have {sorted(wl)}")
    w = wl[name]
    cfgs = {c["name"]: c for c in spec["configs"]}
    config = read_json(os.path.join(root, cfgs[w["config"]]["file"]))
    bench_dir = os.path.join(root, spec["paths"][0])
    traffic = read_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    limits = read_json(os.path.join(bench_dir, "limits", name + ".json"))
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(root=root, spec=spec, workload=w, config=config,
                traffic=traffic, limits=limits, metrics_e2e=e2e,
                metrics_layer=layer)


def driver_for(cell: Cell):
    bench_dir = os.path.join(cell.root, cell.spec["paths"][0])
    return load_module(os.path.join(bench_dir, "drivers",
                                    cell.traffic["driver"] + ".py"))


# ---------------------------------------------------------------------------
# Seeds: any whole number up to a little over 2**31, and more
# ---------------------------------------------------------------------------

def np_rng(seed: int, *stream):
    """A numpy Generator for one named stream of one run's seed."""
    import numpy as np
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    for s in stream:
        if isinstance(s, str):
            words.append(sum((i + 1) * ord(c) for i, c in enumerate(s)))
        else:
            words.append(int(s) & 0xFFFFFFFF)
    return np.random.default_rng(words)


def jax_key(seed: int, stream: int = 0):
    """A JAX key from a seed wider than 32 bits, without x64."""
    import jax
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (int(seed) >> 31) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


# ---------------------------------------------------------------------------
# Device, compile cache
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    """The platform JAX found; exits where it is not a TPU with enough
    chips (tests drive the drivers directly, past this check)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"perfbench: needs a TPU, JAX found platform "
                         f"{info['platform']!r}")
    if len(devs) < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return info


def enable_cache() -> str:
    """JAX's persistent compile cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def peak_memory_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest chip."""
    import jax
    best = None
    for d in jax.devices():
        stats = d.memory_stats() or {}
        v = stats.get("peak_bytes_in_use")
        if v is not None:
            best = v if best is None else max(best, v)
    return best


class CompileCounter:
    """Counts backend compiles while ``active`` (none should fall in a
    measured window)."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0

        def listener(event, duration, **kw):
            if self.active and "backend_compile" in event:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listener)


# ---------------------------------------------------------------------------
# Peaks
# ---------------------------------------------------------------------------

def peaks(kind: str) -> dict:
    table = read_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


# ---------------------------------------------------------------------------
# Statistics
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (failed requests) sort
    last, so a tail that reaches them reads ``inf``."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = max(0, math.ceil(q / 100.0 * len(xs)) - 1)
    return xs[k]


# ---------------------------------------------------------------------------
# Per-layer metrics
# ---------------------------------------------------------------------------

@dataclass
class LayerContext:
    """What a per-layer metric reader may read."""
    cell: Cell
    trace: Any = None                     # trace.Trace of the traced window
    counters: Dict[str, Any] = field(default_factory=dict)
    peaks: Dict[str, float] = field(default_factory=dict)


def reader_path(bench_dir: str, name: str) -> str:
    """The reader of a per-layer metric: ``metrics/<name>.py``, or that
    of the longest base name with a reader, the cell's suffix dropped."""
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        path = os.path.join(bench_dir, "metrics",
                            ".".join(parts[:k]) + ".py")
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no reader for per-layer metric {name!r}")


def read_layer_metrics(ctx: LayerContext) -> Dict[str, dict]:
    bench_dir = os.path.join(ctx.cell.root, ctx.cell.spec["paths"][0])
    out = {}
    for m in ctx.cell.metrics_layer:
        mod = load_module(reader_path(bench_dir, m["name"]))
        value = mod.read(ctx)
        if value is None:
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# Correctness and the result line
# ---------------------------------------------------------------------------

def e2e_metrics(cell: Cell, values: Dict[str, float]) -> Dict[str, dict]:
    """The cell's end-to-end metrics from a driver's values, matched by
    the name before the first dot (``round_s.sync`` reads ``round_s``)."""
    out = {}
    for m in cell.metrics_e2e:
        base = m["name"].split(".")[0]
        if base in values:
            out[m["name"]] = {"value": float(values[base]), "unit": m["unit"]}
    return out


def judge(readings: Dict[str, float], limits: Dict[str, dict]
          ) -> Dict[str, dict]:
    """Each compared number beside its limit; a missing or non-finite
    reading fails."""
    out = {}
    for name, lim in limits.items():
        v = readings.get(name)
        ok = v is not None and math.isfinite(v) and v <= lim["limit"]
        out[name] = {"value": v, "limit": lim["limit"], "ok": bool(ok)}
    return out


def emit(result: dict, checks: Dict[str, dict]) -> None:
    """Checks last on stderr and last in the result line; the line is
    the last line of stdout."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = {n: {"value": c["value"], "limit": c["limit"]}
                      for n, c in checks.items()}
    print(json.dumps(line), flush=True)
