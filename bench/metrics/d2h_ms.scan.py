"""Batched decode: mean seconds to copy one dispatch group's decoded stream
from the device into host memory (`tasm.decode.d2h`)."""
from spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "tasm.decode.d2h")
