"""Jit'd public wrappers for the batched multi-tile decode, with the shared
power-of-two size bucketing that bounds jit traces across arbitrary layouts.

Every block-count-shaped entry point (these ops, the single-tile DCT/IDCT
ops) pads its stream length to :func:`pad_bucket` — the next power of two —
so the number of distinct compiled shapes grows logarithmically with the
largest batch ever seen instead of linearly with every distinct tile
layout.  Callers that assemble the stream themselves (``codec.batch``)
allocate at the bucket size directly so padding costs nothing.

Two entry points share one jitted program, ``_decode_fused``:

- :func:`decode_fused_op` decodes a ready ``[F, M, 8, 8]`` block stream;
- :func:`decode_canvas_op` decodes the stream and, in the same program,
  copies the frames back in canvas order (:func:`copy_back`), steered by a
  column table (:func:`column_table`) of one word a column.

**Copy-back layout** (``[F, M * 64]`` float32, one plane a frame depth).
Plane ``f`` holds frame ``f`` of every GOP.  A slot that starts at column
``off`` owns positions ``[off * 64, (off + span) * 64)`` of each plane:
its ``G`` GOPs in order, each a ``[H8 * 8, W8 * 8]`` raster — a full
tile's canvas (``W8`` = width / 8) or, for a block mask, the selected
blocks in order (``W8`` = 1, ``H8`` = blocks a GOP).  The permutation
within a plane is the same for every frame, so the program moves each
8-float row segment with all ``F`` frames at once.  Planes at or past a
slot's depth hold what the stream decodes there and are never read.

**Column table** (``[M]`` uint32, the only index data sent): bit 31 marks
a slot's first column, bits 20–30 hold ``W8`` and bits 0–19 ``H8``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode.decode import decode_gop_blocks
from repro.kernels.decode.ref import decode_fused_ref

#: floor for the padded column count — tiny batches share one trace
MIN_COLUMNS = 64
#: column-table fields (module doc): raster width and height in blocks
MAX_W8 = (1 << 11) - 1
MAX_H8 = (1 << 20) - 1


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


def column_table(m: int, slots) -> np.ndarray:
    """The ``[m]`` uint32 column table (module doc) of ``slots``: ``(off,
    span, w8, h8)`` each, in column order.  Columns past the last slot are
    one-block slots."""
    if m >= 1 << 21:
        raise ValueError(f"{m} columns: the copy back indexes below 2**24")
    tab = np.empty(m, dtype=np.uint32)
    end = 0
    for off, span, w8, h8 in slots:
        if not (1 <= w8 <= MAX_W8 and 1 <= h8 <= MAX_H8):
            raise ValueError(f"a raster of {h8}x{w8} blocks does not fit "
                             "the column table")
        tab[off:off + span] = (w8 << 20) | h8
        tab[off] |= 1 << 31
        end = off + span
    tab[end:] = (1 << 31) | (1 << 20) | 1
    return tab


def _divmod(a: jnp.ndarray, b: jnp.ndarray):
    """Floor division of non-negative int32 below 2**24 through float32,
    corrected to exact: XLA's integer division compiles slowly here."""
    q = jnp.floor(a.astype(jnp.float32) / b.astype(jnp.float32))
    q = q.astype(jnp.int32)
    r = a - q * b
    q = jnp.where(r < 0, q - 1, jnp.where(r >= b, q + 1, q))
    return q, a - q * b


def copy_back(out: jnp.ndarray, tab: jnp.ndarray) -> jnp.ndarray:
    """Decoded ``[F, M, 8, 8]`` frames + column table -> the flat
    copy-back planes (module doc)."""
    f_depth, m = out.shape[:2]
    col = jnp.arange(m, dtype=jnp.int32)
    off = jax.lax.cummax(jnp.where((tab >> 31) == 1, col, 0), axis=0)
    w8 = ((tab >> 20) & MAX_W8).astype(jnp.int32)
    nb = (tab & MAX_H8).astype(jnp.int32) * w8
    # segment i of plane column c comes from row ``src[i, c]`` of ``rows``
    g, u = _divmod(col - off, nb)
    y, bx = _divmod(u * 8 + jnp.arange(8, dtype=jnp.int32)[:, None], w8)
    src = ((off + g * nb) + (y >> 3) * w8 + bx) * 8 + (y & 7)
    rows = out.transpose(1, 2, 0, 3).reshape(m * 8, f_depth * 8)
    rows = jnp.take(rows, src.ravel(), axis=0, mode="clip")
    return rows.reshape(8, m, f_depth, 8).transpose(2, 1, 0, 3).reshape(-1)


def _decode(q, qp, use_pallas, interpret):
    if use_pallas:
        return decode_gop_blocks(q, qp, interpret=interpret)
    return decode_fused_ref(q, qp)


@functools.partial(jax.jit, static_argnames=("qp", "use_pallas", "interpret"))
def _decode_fused(q: jnp.ndarray, tab: jnp.ndarray | None = None, *, qp: int,
                  use_pallas: bool, interpret: bool) -> jnp.ndarray:
    if tab is None:
        return _decode(q, qp, use_pallas, interpret)
    stream = q.reshape(-1, tab.shape[0], 8, 8)
    return copy_back(_decode(stream, qp, use_pallas, interpret), tab)


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


def decode_canvas_op(q: np.ndarray, tab: np.ndarray, *, qp: int,
                     use_pallas: bool | None = None,
                     interpret: bool = False) -> jnp.ndarray:
    """The flat ``[F * M * 64]`` int16 stream + column table -> the flat
    float32 copy-back planes (module doc), in one program: the stream
    decoded as :func:`decode_fused_op` decodes it, then :func:`copy_back`.
    ``M`` (the table's length) must be a bucket of :func:`pad_bucket`."""
    if use_pallas is None:
        use_pallas = use_pallas_default()
    return _decode_fused(q, tab, qp=qp, use_pallas=bool(use_pallas),
                         interpret=interpret)


#: the copy back alone, for a stream decode run outside the program
copy_back_op = jax.jit(copy_back)
