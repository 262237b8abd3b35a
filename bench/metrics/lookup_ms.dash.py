"""Planning: mean semantic-index lookup time of a dashboard's selection."""
from records import mean_ms


def read(ctx):
    return mean_ms(ctx, "sel", "lookup_s")
