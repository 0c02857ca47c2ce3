"""Device time of the operations a ``jax.named_scope`` encloses, from a
traced stretch and the compiled program's HLO.

A device operation event is named by its HLO instruction
(``%fusion.12 = ...``) and carries no metadata; the scope lives in the
instruction's ``op_name`` in the compiled module's text (e.g.
``jit(local_train)/.../moe.experts/scatter-add``; a fusion carries its root
operation's). So each event inside an execution of the module is looked
up by its instruction name, and a scope's time is the union of its
events' intervals: a loop and the operations it runs are nested events,
and counting both would count that time twice.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Sequence

import xtrace

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _in_scope(op_name: str, scope: str) -> bool:
    return re.search(r"(^|[/(])" + re.escape(scope) + r"($|[/)])",
                     op_name) is not None


def scope_seconds(trace, hlo_text: str, module: str,
                  scopes: Sequence[str],
                  ops: Sequence[str] = ()) -> Dict[str, float]:
    """Seconds, per scope, in which an operation of ``module`` that the
    scope encloses ran inside the trace's window, averaged over the chips;
    ``ops`` adds, per pattern, the operations whose name holds it (e.g.
    ``ragged-dot``)."""
    names = op_names(hlo_text)
    inside = trace._inside((module,))
    spans = defaultdict(lambda: defaultdict(list))
    for p, l, name, s, d, m in trace.events:
        if not (p.startswith(xtrace.DEVICE_PREFIX) and l == xtrace.OPS_LINE
                and trace._clip(s, d) > 0 and inside(p, s)):
            continue
        short = xtrace.short_name(name).lstrip("%")
        op = names.get(short, "")
        iv = (max(s, trace.t0), min(s + d, trace.t1))
        for sc in scopes:
            if op and _in_scope(op, sc):
                spans[sc][p].append(iv)
        for o in ops:
            if o in short:
                spans[o][p].append(iv)
    planes = max(len(trace.device_planes), 1)
    return {k: sum(e - s for p in (spans[k] if k in spans else {})
                   for s, e in xtrace._union(spans[k][p])) / planes / 1e9
            for k in (*scopes, *ops)}
