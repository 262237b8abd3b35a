"""Tile store: pixels decoded for the selections over the pixels of the
regions they returned."""
from records import ratio


def read(ctx):
    return ratio(ctx, "sel", "pixels_decoded", "region_px")
