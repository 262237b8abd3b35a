"""Wire: mean milliseconds of a node's marshal of a reply to the router
(`tasm.marshal` over every node of the cluster)."""
from spans import nodes_mean_ms


def read(ctx):
    return nodes_mean_ms(ctx, "tasm.marshal")
