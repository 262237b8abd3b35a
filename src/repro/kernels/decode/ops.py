"""Jit'd public wrapper for the batched multi-tile decode, with the shared
power-of-two size bucketing that bounds jit traces across arbitrary layouts.

Every block-count-shaped entry point (this op, the single-tile DCT/IDCT
ops) pads its stream length to :func:`pad_bucket` — the next power of two —
so the number of distinct compiled shapes grows logarithmically with the
largest batch ever seen instead of linearly with every distinct tile
layout.  Callers that assemble the stream themselves (``codec.batch``)
allocate at the bucket size directly so padding costs nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode.decode import decode_gop_blocks
from repro.kernels.decode.ref import decode_fused_ref

#: floor for the padded column count — tiny batches share one trace
MIN_COLUMNS = 64


def pad_bucket(n: int, lo: int = 8) -> int:
    """Smallest power of two >= max(n, lo): the shared jit-size bucket.

    Padding every variable block/column count up to a bucket keeps the
    number of distinct jit traces bounded (one per octave) no matter how
    many distinct tile shapes a workload produces."""
    if n <= lo:
        return lo
    return 1 << (int(n) - 1).bit_length()


def use_pallas_default() -> bool:
    """The Pallas kernel path is the default on TPU only; everywhere else
    the jitted jnp fused path (XLA) is both correct and faster."""
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("qp", "use_pallas", "interpret"))
def _decode_fused(q: jnp.ndarray, *, qp: int, use_pallas: bool,
                  interpret: bool) -> jnp.ndarray:
    if use_pallas:
        return decode_gop_blocks(q, qp, interpret=interpret)
    return decode_fused_ref(q, qp)


def decode_fused_op(q: jnp.ndarray, *, qp: int,
                    use_pallas: bool | None = None,
                    interpret: bool = False) -> jnp.ndarray:
    """[F, M, 8, 8] int16 -> [F, M, 8, 8] f32 reconstructed frames.

    Row 0 is dequantized with the intra matrix, rows 1+ with the inter
    matrix, each block IDCT'd, then summed cumulatively over F (the
    closed-loop GOP reconstruction).  Matches the numpy ``decode_tile``
    arithmetic per column to f32 rounding (the contractions run at
    ``HIGHEST`` precision; see ``repro/kernels/decode/ref.py``).

    M is padded to :func:`pad_bucket` columns (zero coefficients decode to
    zero pixels, sliced off before return), F is used as-is — callers
    bucket it (``codec.batch`` pads GOP depth with trailing zero-coefficient
    frames, which never perturb the leading cumulative sums).
    """
    m = q.shape[1]
    mp = pad_bucket(m, lo=MIN_COLUMNS)
    if mp != m:
        q = jnp.concatenate(
            [q, jnp.zeros((q.shape[0], mp - m, 8, 8), q.dtype)], axis=1)
    if use_pallas is None:
        use_pallas = use_pallas_default()
    out = _decode_fused(q, qp=qp, use_pallas=bool(use_pallas),
                        interpret=interpret)
    return out[:, :m]
