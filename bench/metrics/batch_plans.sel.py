"""Scheduler: mean number of plans a served batch hands to one
`execute_many` (`tasm.batch_plans`)."""
from spans import mean


def read(ctx):
    return mean(ctx, "tasm.batch_plans")
