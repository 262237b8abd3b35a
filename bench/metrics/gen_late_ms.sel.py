"""Load generator: 95th percentile of how late each selection was sent
after it was due."""
import numpy as np

from records import of


def read(ctx):
    late = [r["sent"] - (ctx.t0 + r["due"]) for r in of(ctx, "sel")]
    return float(np.percentile(late, 95)) * 1e3 if late else None
