"""Reductions shared by per-layer metric readers (each reader in
``metrics/`` names its own inputs; these do the arithmetic)."""
from __future__ import annotations


def _device(ctx):
    """A trace with device events and a window, or None."""
    t = ctx.trace
    return t if t is not None and t.device_planes and t.window_s > 0 \
        else None


def idle_share(ctx):
    if _device(ctx) is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)


def span_mean_ms(ctx, name):
    """Mean of a host span of the program inside the traced window."""
    if ctx.trace is None:
        return None
    d = ctx.trace.span_durations(name)
    return 1e3 * sum(d) / len(d) if d else None


def module_ms_per_round(ctx, modules):
    """Device milliseconds per traced round of the modules named."""
    n = ctx.counters.get("rounds_traced")
    if ctx.trace is None or not n:
        return None
    secs, calls = ctx.trace.module_time(modules)
    return 1e3 * secs / n if calls else None


def fed_mfu_pct(ctx):
    """The traced rounds' required FLOPs over their wall time x peak."""
    n = ctx.counters.get("rounds_traced")
    f = ctx.counters.get("flops_per_round")
    if _device(ctx) is None or not n or not f:
        return None
    return 100.0 * f * n / (ctx.trace.window_s * ctx.peaks["bf16_flops"])


def module_ms_per_call(ctx, modules):
    if ctx.trace is None:
        return None
    secs, n = ctx.trace.module_time(modules)
    return 1e3 * secs / n if n else None


def mfu_pct(ctx):
    f = ctx.counters.get("flops")
    if _device(ctx) is None or not f:
        return None
    return 100.0 * f / (ctx.trace.window_s * ctx.peaks["bf16_flops"])
