"""Batched decode: mean seconds to scatter one dispatch group's decoded
stream into its tiles' canvases (`tasm.decode.scatter`)."""
from spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "tasm.decode.scatter")
