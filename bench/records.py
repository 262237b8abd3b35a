"""Reductions of the load generator's request records that metrics share.

A record is one request: ``cls`` ("sel" or "scan"), ``due`` (seconds from
the window's start), ``sent`` and ``done`` (monotonic seconds), and either
``stats`` (the reply's ``ScanStats`` plus ``region_px``, the pixels of the
regions it returned) or ``error``.  A record without ``done`` never got an
answer.
"""
from __future__ import annotations

import numpy as np


def of(ctx, cls: str) -> list[dict]:
    return [r for r in ctx.records if r["cls"] == cls]


def answered(ctx, cls: str) -> list[dict]:
    return [r for r in of(ctx, cls) if "stats" in r]


def latencies_s(ctx, cls: str) -> list[float]:
    """Seconds from when each request was due to its answer.  A request
    that failed or never came counts as answered at the end of the wait
    past the window: it misses any latency limit."""
    cap = ctx.t0 + ctx.seconds + ctx.grace
    return [(r["done"] if "stats" in r else cap) - (ctx.t0 + r["due"])
            for r in of(ctx, cls)]


def percentile_ms(ctx, cls: str, q: float):
    lat = latencies_s(ctx, cls)
    return float(np.percentile(lat, q)) * 1e3 if lat else None


def mean_ms(ctx, cls: str, field: str, only_nonzero: bool = False):
    vals = [r["stats"][field] for r in answered(ctx, cls)]
    if only_nonzero:
        vals = [v for v in vals if v > 0]
    return float(np.mean(vals)) * 1e3 if vals else None


def ratio(ctx, cls: str, num: str, den: str):
    rs = answered(ctx, cls)
    d = sum(r["stats"][den] for r in rs)
    return sum(r["stats"][num] for r in rs) / d if d else None


def delivered_mpx_s(ctx, cls: str):
    """Megapixels of the regions answered per second of the window.  A
    reply that straddles the window's end is credited with the share of its
    time inside the window."""
    lo, hi = ctx.t0, ctx.t0 + ctx.seconds
    px = 0.0
    for r in answered(ctx, cls):
        span = r["done"] - r["sent"]
        inside = min(r["done"], hi) - max(r["sent"], lo)
        if span > 0 and inside > 0:
            px += r["stats"]["region_px"] * inside / span
    return px / ctx.seconds / 1e6 if px else None


def idle_pct(ctx):
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def decode_roofline_pct(ctx):
    """The decoded pixels of the traced window at ``bytes_per_pixel`` each,
    over peak HBM bandwidth, as a share of the decode kernel's own device
    time in the trace (``kernel_s``: not the copy back or the relayouts
    that the decode program runs around it)."""
    import trace_reduce

    t = ctx.trace
    if t is None or t["kernel_s"] <= 0 or ctx.pixels_in_window <= 0:
        return None
    return trace_reduce.roofline_pct(ctx.pixels_in_window
                                     * ctx.bytes_per_pixel,
                                     t["kernel_s"], ctx.device_kind)
