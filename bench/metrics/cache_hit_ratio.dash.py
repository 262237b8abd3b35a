"""Tile cache: tiles served from the cache over tiles fetched, summed over
the selections answered."""
from records import answered


def read(ctx):
    rs = answered(ctx, "sel")
    hits = sum(r["stats"]["cache_hits"] for r in rs)
    total = hits + sum(r["stats"]["cache_misses"] for r in rs)
    return hits / total if total else None
