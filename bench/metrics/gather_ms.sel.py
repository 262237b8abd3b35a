"""Batched decode: mean seconds to gather one dispatch group's int16 block
stream on the host (`tasm.decode.gather`)."""
from spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "tasm.decode.gather")
