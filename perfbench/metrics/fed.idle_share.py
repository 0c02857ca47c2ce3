"""Percent of the traced rounds in which no operation ran on the
device: 100 * (1 - busy / window)."""
import readers


def read(ctx):
    return readers.idle_share(ctx)
