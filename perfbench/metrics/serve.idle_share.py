"""Percent of the traced window in which no operation ran on the device;
the breakdown attributes each idle stretch to the host span it fell in."""
import readers


def read(ctx):
    return readers.idle_share(ctx)
