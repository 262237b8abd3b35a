"""Scheduler: mean seconds a selection waited in the serving queue, from
its submission to the start of the batch that took it (`tasm.queue`)."""
from spans import mean_ms


def read(ctx):
    return mean_ms(ctx, "tasm.queue")
