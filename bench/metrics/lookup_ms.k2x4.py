"""Planning: mean milliseconds of a node's index lookup (`tasm.plan` over
every node of the cluster)."""
from spans import nodes_mean_ms


def read(ctx):
    return nodes_mean_ms(ctx, "tasm.plan")
