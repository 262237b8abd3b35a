"""Router: mean milliseconds of the router's re-marshal of a relayed reply
(`tasm.marshal` in the process that serves the router)."""
from spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "tasm.marshal")
