"""The program's own spans and counters of the measured window, for the
per-layer readers.

The program records them in memory (``repro.utils.trace``) with
``time.monotonic()`` stamps, the clock of ``ctx.t0``; a record belongs to
the window when it ended inside it.  A program without the recorder, or
one that dropped records of the window, gives ``None``: the metric is then
left out, never reported from part of the window.
"""
from __future__ import annotations


def named(ctx, name: str):
    """Values of the window's records called ``name`` (a span's seconds, a
    counter's samples), or ``None``."""
    try:
        from repro.utils import trace
    except ImportError:
        return None
    recs = trace.window(ctx.t0, ctx.t0 + ctx.seconds)
    if recs is None:
        return None
    return [r.value for r in recs if r.name == name]


def mean(ctx, name: str, scale: float = 1.0):
    vals = named(ctx, name)
    return scale * sum(vals) / len(vals) if vals else None


def mean_ms(ctx, name: str):
    return mean(ctx, name, 1e3)


def nodes_mean_ms(ctx, name: str):
    """Mean milliseconds of the spans called ``name`` over every node of a
    cluster cell (``ctx.node_spans``: each node's window records as
    ``[name, value]``), or ``None`` where a node dropped records of the
    window or none ran."""
    if any(recs is None for recs in ctx.node_spans):
        return None
    vals = [v for recs in ctx.node_spans for n, v in recs if n == name]
    return 1e3 * sum(vals) / len(vals) if vals else None
