"""Device milliseconds per traced round inside the program's ``moe.route``
and ``moe.experts`` scopes (``models/moe.py``: routing, sorting, the held
experts' grouped matmuls and the combine), attributed from the capture by
``scopes.py``; None where the program has no such scope."""


def read(ctx):
    v = ctx.counters.get("moe_s_per_round")
    return 1e3 * v if v else None
