"""Device milliseconds per round of the aggregation engine
(``core/agg_engine.py``), from the trace. The engine jits a
``functools.partial``, which XLA names ``jit__unknown``."""
import readers


def read(ctx):
    return readers.module_ms_per_round(ctx, ("jit__unknown",))
