"""Decode kernel: share of its bandwidth roofline in a selection cell."""
from records import decode_roofline_pct


def read(ctx):
    return decode_roofline_pct(ctx)
