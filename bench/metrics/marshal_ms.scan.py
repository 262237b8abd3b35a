"""Wire: mean seconds the server spent marshalling a scan reply."""
from records import mean_ms


def read(ctx):
    return mean_ms(ctx, "scan", "marshal_s")
