"""clock-discipline: every timestamp flows through ``Recorder``.

The observability layer rebases child processes onto the parent's
timeline with one sanctioned wall-clock handshake
(``Recorder.wall()``); any other raw clock read forks the timeline off
the recorder's shared ``perf_counter`` origin and silently corrupts
cross-process traces, SLO windows, and the perf history. The old
tier-1 lint grepped for the literal substrings ``time.time(`` /
``time.perf_counter(`` — so even a docstring *mentioning* the call
counted, and aliased imports slipped through. This pass matches real
call sites on the AST, nothing else.

Scope is all of ``src/repro`` (the grep only covered serve/fed/obs).
Sanctioned sites: ``obs/recorder.py`` (the clock owner — allowlisted
in :data:`~repro.analysis.framework.ALLOWLIST`) and pragma'd lines in
``launch/train.py`` (a standalone CLI reporting wall-clock progress
with no recorder in scope) and the
``core/agg_engine.py`` autotune probe (a one-shot timing *measurement*
whose result is a backend choice, not a recorded event).
"""
from __future__ import annotations

import ast
from typing import Iterable

from repro.analysis.framework import (Finding, LintPass, ModuleContext,
                                      dotted_name, register)

#: canonical dotted names of raw clock reads
CLOCK_CALLS = frozenset({
    "time.time", "time.time_ns",
    "time.perf_counter", "time.perf_counter_ns",
    "time.monotonic", "time.monotonic_ns",
    "time.process_time", "time.process_time_ns",
    "time.clock_gettime", "time.clock_gettime_ns",
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "datetime.datetime.today", "datetime.date.today",
})


@register
class ClockDiscipline(LintPass):
    name = "clock-discipline"
    description = ("raw clock reads (time.time/perf_counter/datetime.now "
                   "...) outside obs/recorder.py fork the shared timeline")
    hint = ("route timestamps through Recorder.now() (monotonic) or "
            "Recorder.wall() (the one sanctioned wall-clock read)")

    def findings(self, ctx: ModuleContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func, ctx.imports)
            if name in CLOCK_CALLS:
                yield self.finding(
                    ctx, node,
                    f"raw clock read {name}() — every timestamp must "
                    f"come from the shared Recorder clock")
