"""Whole-step share of the chip's bf16 peak: the required FLOPs of every
prefill and decode token served in the traced window
(``flops.prefill_flops``, ``flops.decode_flops``) over its length."""
import readers


def read(ctx):
    return readers.mfu_pct(ctx)
