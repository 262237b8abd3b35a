"""Device: the nodes' mean share of the traced window in which no op ran on
their chip."""


def read(ctx):
    ts = ctx.node_traces
    if not ts or any(t is None or t["window_s"] <= 0 for t in ts):
        return None
    return sum(100.0 * (1.0 - t["busy_s"] / t["window_s"])
               for t in ts) / len(ts)
