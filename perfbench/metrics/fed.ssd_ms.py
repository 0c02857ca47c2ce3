"""Device milliseconds per traced round inside the program's ``ssm.ssd``
scope (``models/mamba2.py``: the chunked state-space scan of every Mamba
layer, forward and backward), attributed from the capture by
``scopes.py``; None where the program has no such scope."""


def read(ctx):
    v = ctx.counters.get("ssd_s_per_round")
    return 1e3 * v if v else None
