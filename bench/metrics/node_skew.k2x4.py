"""Placement: the busiest node's decoded pixels in the window over the
nodes' mean (1.0 is even)."""


def read(ctx):
    px = ctx.node_pixels
    mean = sum(px) / len(px)
    return max(px) / mean if mean > 0 else None
