"""Uplink plus downlink bytes per round, as the session measured the
serialized messages (``fed/messages.py``; the round's history)."""


def read(ctx):
    return ctx.counters.get("wire_bytes_per_round")
