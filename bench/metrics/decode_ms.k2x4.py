"""Batched decode: mean milliseconds of a group fetch (`tasm.fetch`) over
every node of the cluster."""
from spans import nodes_mean_ms


def read(ctx):
    return nodes_mean_ms(ctx, "tasm.fetch")
