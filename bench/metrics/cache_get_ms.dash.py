"""Tile cache: mean seconds of one group fetch's cache lookups, with the
materializing of its hits (`tasm.cache.get`)."""
from spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "tasm.cache.get")
