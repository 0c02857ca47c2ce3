"""Device-idle time inside the program's own host spans, per traced
round: the arithmetic of the wire readers beside this file
(``fed.downlink_idle_ms.py``, ``fed.uplink_idle_ms.py``)."""
import readers
import xtrace


def _overlap(a, b) -> float:
    """Total length shared by two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_ms_per_round(ctx, name: str):
    """Milliseconds per traced round in which the first chip is idle
    inside a host span ``name``: the trace's idle gaps intersected with
    the union of those spans, over ``rounds_traced``. None without a
    device trace or traced rounds, or where the program records no such
    span in the window."""
    t, n = readers._device(ctx), ctx.counters.get("rounds_traced")
    if t is None or not n:
        return None
    spans = xtrace._union((max(s, t.t0), min(e, t.t1))
                          for nm, s, e in t.host_spans([name])
                          if nm == name and s < t.t1 and e > t.t0)
    if not spans:
        return None
    return _overlap(t.idle_gaps(), spans) / 1e6 / n
