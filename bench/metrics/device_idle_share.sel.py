"""Device: share of the traced window in which no op ran, in a selection
cell."""
from records import idle_pct


def read(ctx):
    return idle_pct(ctx)
