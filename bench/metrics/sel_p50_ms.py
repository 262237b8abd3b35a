"""Median selection latency, from when each was due, over all selections
due in the window."""
from records import percentile_ms


def read(ctx):
    return percentile_ms(ctx, "sel", 50)
