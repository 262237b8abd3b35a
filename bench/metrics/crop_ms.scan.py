"""Crop: mean seconds to cut one plan's regions out of its decoded tiles
(`tasm.crop`)."""
from spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "tasm.crop")
