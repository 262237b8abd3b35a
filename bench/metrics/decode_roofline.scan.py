"""Decode kernel: share of its bandwidth roofline in a scan cell."""
from records import decode_roofline_pct


def read(ctx):
    return decode_roofline_pct(ctx)
