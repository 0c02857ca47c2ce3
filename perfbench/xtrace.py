"""Reduction of a profiler capture to the numbers the per-layer metrics
read: device busy time (the union of operation intervals), device time
per XLA module and per operation, idle gaps attributed to the host span
they fall in, and the ``breakdown`` of the result line.

A capture is flattened to plain events ``(plane, line, name, start_ns,
dur_ns, module)`` first, so a small recorded trace can be kept as JSON
and the reduction tested without a chip.
"""
from __future__ import annotations

import glob
import json
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float, str]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO instruction on the TPU;
    keep what precedes ' = ' ('%fusion.12')."""
    return name.split(" = ", 1)[0]


def load_xspace(trace_dir: str) -> List[Event]:
    """Every device event and every host python-thread event of the
    newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in pd.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        if not is_dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            if not is_dev and not line.name.startswith("python"):
                continue
            for ev in line.events:
                module = ""
                if is_dev and line.name == OPS_LINE:
                    for k, v in ev.stats:
                        if k == "hlo_module":
                            module = str(v)
                            break
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns),
                            module))
    return out


def save_events(events: Sequence[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([list(e) for e in events], f)


def load_events(path: str) -> List[Event]:
    with open(path) as f:
        return [tuple(e) for e in json.load(f)]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class Trace:
    """The events of one traced window, clipped to ``[t0, t1)`` (ns on
    the profiler's clock; the window's host span gives them)."""

    def __init__(self, events: Sequence[Event], window_span: str = "",
                 window: Optional[Tuple[float, float]] = None):
        self.events = list(events)
        if window is None and window_span:
            spans = [(s, s + d) for p, l, n, s, d, m in self.events
                     if p == HOST_PLANE and n == window_span]
            if spans:
                window = (min(s for s, _ in spans),
                          max(e for _, e in spans))
        if window is None:
            dev = [(s, s + d) for s, d in self._dev(OPS_LINE)]
            window = (min(s for s, _ in dev), max(e for _, e in dev)) \
                if dev else (0.0, 0.0)
        self.t0, self.t1 = window

    # -- selections ---------------------------------------------------------

    def _dev(self, line: str, plane: Optional[str] = None):
        return [(s, d) for p, l, n, s, d, m in self.events
                if p.startswith(DEVICE_PREFIX) and l == line
                and (plane is None or p == plane)]

    def _clip(self, s: float, d: float) -> float:
        return max(0.0, min(s + d, self.t1) - max(s, self.t0))

    @property
    def device_planes(self) -> List[str]:
        return sorted({p for p, l, n, s, d, m in self.events
                       if p.startswith(DEVICE_PREFIX) and l == OPS_LINE})

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_intervals(self, plane: str) -> List[List[float]]:
        return _union((max(s, self.t0), min(s + d, self.t1))
                      for s, d in self._dev(OPS_LINE, plane)
                      if s < self.t1 and s + d > self.t0)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        planes = self.device_planes
        if not planes:
            return 0.0
        tot = sum(e - s for p in planes for s, e in self.busy_intervals(p))
        return tot / len(planes) / 1e9

    # -- per module / per operation ----------------------------------------

    def module_time(self, patterns: Sequence[str]) -> Tuple[float, int]:
        """(seconds, executions) of the XLA modules whose name holds any
        of ``patterns``, averaged over the chips."""
        planes = max(len(self.device_planes), 1)
        tot, n = 0.0, 0
        for p, l, name, s, d, m in self.events:
            if (p.startswith(DEVICE_PREFIX) and l == MODULES_LINE
                    and any(pat in name for pat in patterns)):
                c = self._clip(s, d)
                if c > 0:
                    tot += c
                    n += 1
        return tot / planes / 1e9, n // planes

    def _inside(self, modules: Sequence[str]):
        """Per plane, the sorted intervals of the modules named."""
        import bisect
        spans = defaultdict(list)
        for p, l, name, s, d, m in self.events:
            if (p.startswith(DEVICE_PREFIX) and l == MODULES_LINE
                    and any(mm in name for mm in modules)):
                spans[p].append((s, s + d))
        for p in spans:
            spans[p].sort()
        starts = {p: [a for a, _ in v] for p, v in spans.items()}

        def inside(p, s):
            k = bisect.bisect_right(starts.get(p, []), s) - 1
            return k >= 0 and spans[p][k][1] >= s
        return inside

    def op_time(self, patterns: Sequence[str],
                modules: Sequence[str] = ()) -> Tuple[float, int]:
        """(seconds, executions) of operations whose name holds any of
        ``patterns`` (inside an execution of a module whose name holds
        any of ``modules``, if given), averaged over the chips."""
        planes = max(len(self.device_planes), 1)
        inside = self._inside(modules) if modules else None
        tot, n = 0.0, 0
        for p, l, name, s, d, m in self.events:
            if (p.startswith(DEVICE_PREFIX) and l == OPS_LINE
                    and any(pat in name for pat in patterns)
                    and (inside is None or inside(p, s))):
                c = self._clip(s, d)
                if c > 0:
                    tot += c
                    n += 1
        return tot / planes / 1e9, n // planes

    def top_ops(self, n: int = 10) -> List[List]:
        planes = max(len(self.device_planes), 1)
        acc: Dict[str, float] = defaultdict(float)
        for p, l, name, s, d, m in self.events:
            if p.startswith(DEVICE_PREFIX) and l == OPS_LINE:
                c = self._clip(s, d)
                if c > 0:
                    acc[f"{m}/{short_name(name)}" if m
                        else short_name(name)] += c
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / planes / 1e9] for k, v in top]

    # -- host spans and idle gaps ------------------------------------------

    def host_spans(self, prefixes: Sequence[str] = ()) -> List[Tuple]:
        return [(n, s, s + d) for p, l, n, s, d, m in self.events
                if p == HOST_PLANE and d > 0
                and (not prefixes or any(n.startswith(x) for x in prefixes))]

    def span_durations(self, name: str) -> List[float]:
        """Seconds of each host span ``name`` inside the window."""
        return [(e - s) / 1e9 for n, s, e in self.host_spans([name])
                if n == name and s >= self.t0 and e <= self.t1]

    def idle_gaps(self, min_s: float = 0.0) -> List[Tuple[float, float]]:
        """Idle intervals of the first chip inside the window."""
        planes = self.device_planes
        if not planes:
            return [(self.t0, self.t1)]
        gaps, cur = [], self.t0
        for s, e in self.busy_intervals(planes[0]):
            if s - cur > min_s * 1e9:
                gaps.append((cur, s))
            cur = max(cur, e)
        if self.t1 - cur > min_s * 1e9:
            gaps.append((cur, self.t1))
        return gaps

    def gap_attribution(self, prefixes: Sequence[str],
                        n: int = 10) -> List[List]:
        """Idle seconds by the innermost host span (of ``prefixes``) that
        covers each stretch of idle time; ``(no span)`` where none does."""
        spans = self.host_spans(prefixes)
        acc: Dict[str, float] = defaultdict(float)
        for g0, g1 in self.idle_gaps():
            cuts = sorted({g0, g1} | {x for _, s, e in spans
                                      for x in (s, e) if g0 < x < g1})
            for a, b in zip(cuts, cuts[1:]):
                mid = 0.5 * (a + b)
                inner = [(e - s, name) for name, s, e in spans
                         if s <= mid < e]
                acc[min(inner)[1] if inner else "(no span)"] += b - a
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def breakdown(self, prefixes: Sequence[str]) -> dict:
        return {"device_ops": self.top_ops(10),
                "idle_gaps": self.gap_attribution(prefixes, 10)}
