"""Whole-round share of the chip's bf16 peak: the cohort's required
forward and backward FLOPs (``flops.encoder_round_flops``; the frozen
backbone takes no weight gradient) over the traced rounds' wall time."""
import readers


def read(ctx):
    return readers.fed_mfu_pct(ctx)
