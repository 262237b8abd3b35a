"""Megapixels of selected regions delivered to closed-loop clients per
second of the window."""
from records import delivered_mpx_s


def read(ctx):
    return delivered_mpx_s(ctx, "sel")
