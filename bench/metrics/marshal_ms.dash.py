"""Wire: mean seconds the server spent marshalling a dashboard's reply."""
from records import mean_ms


def read(ctx):
    return mean_ms(ctx, "sel", "marshal_s")
