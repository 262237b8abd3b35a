"""Batched decode: mean decode seconds of the selections that decoded
(gather, dispatch, device, copy back, scatter)."""
from records import mean_ms


def read(ctx):
    return mean_ms(ctx, "sel", "decode_s", only_nonzero=True)
