"""Batched decode: mean decode seconds of the scans that decoded (gather,
dispatch, device, copy back, scatter)."""
from records import mean_ms


def read(ctx):
    return mean_ms(ctx, "scan", "decode_s", only_nonzero=True)
