"""Batched tile decode: many ``(tile, GOP-range, block-mask)`` selections in
one (or a few) fused accelerator dispatches.

``decode_tile_batch`` is the batched counterpart of
:func:`repro.codec.encode.decode_tile` — the numpy path stays the oracle,
and this path matches it item by item within :data:`ORACLE_ATOL` (max abs
difference on 0-255 pixels).  The gap is f32 rounding: the accelerator
sums each 8x8 contraction and the closed-loop reconstruction of up to 32
frames in its own order, so errors of a few ulps of 255 accumulate over a
GOP; a reduced-precision (bf16) contraction misses by orders of magnitude
more.  Within one backend the arithmetic is deterministic, so cache on vs
off and serial vs merged batches stay bit-identical.  Instead of one
einsum call per tile per GOP inside a Python loop, the whole batch is
flattened into a padded block stream of ``[F, M, 8, 8]`` int16 columns
(row 0 the intra keyframe, rows 1..n-1 the inter residuals), one column a
selected 8x8 block of one GOP:

1. **Bucket** — items are grouped by ``(qp, F bucket)``; each group's
   stream has a power-of-two column count
   (:func:`repro.kernels.decode.ops.pad_bucket`) so jit traces stay bounded
   across arbitrary tile layouts.  Frame-depth padding appends zero
   coefficient rows, which decode to zero pixels *after* every real frame
   and are dropped.
2. **Gather** — each selected GOP's stored rows (its keyframe blocks and
   each residual frame's blocks) are copied once, by slices, into their
   place in the stream; ROI block masks are applied *here*, by ``np.take``
   into the stream, so masked-out blocks never reach the accelerator.
   Only the padding is zeroed.
3. **Dispatch** — one program per group runs the fused
   dequant+IDCT+cumsum (the Pallas kernel on TPU, the jitted jnp path
   under XLA elsewhere; both within :data:`ORACLE_ATOL` of numpy — see
   ``repro/kernels/decode``) and copies the frames back in canvas order,
   steered by a table of one word a column (``repro/kernels/decode/ops``).
4. **Scatter** — a full tile's canvas of one GOP (a mask covering every
   block counts as full) is a view of the copied-back planes; more GOPs
   are joined.  An ROI item's blocks come back in order and are scattered
   into a zeroed canvas exactly like the oracle (the same advanced-index
   write, unselected blocks zero).

``decode_fused_op`` is the stream decode the program runs.  Where it is
replaced (a decode at another precision, as ``bench/control.py`` does),
it runs on the same stream and the copy back follows as its own step.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np

from repro.kernels.decode import ops
from repro.kernels.decode.ops import (MIN_COLUMNS, column_table,
                                      copy_back_op, decode_canvas_op,
                                      decode_fused_op, pad_bucket)
from repro.utils import trace

#: max abs difference from the numpy oracle on 0-255 pixels (module doc)
ORACLE_ATOL = 1e-2

#: one decode request: (enc dict, gop_indices, frames_within, blocks) with
#: the exact semantics of ``decode_tile``'s parameters of the same names
DecodeItem = tuple


class _Slot:
    """Where one item's columns live inside its group's block stream."""

    __slots__ = ("item", "n", "n_gops", "bsel", "nb", "offset", "span")

    def __init__(self, item, n, n_gops, bsel, nb, offset, span):
        self.item = item
        self.n = n                  # frames decoded per selected GOP
        self.n_gops = n_gops
        self.bsel = bsel            # None = full tile
        self.nb = nb                # blocks a GOP
        self.offset = offset
        self.span = span

    def raster(self) -> tuple[int, int]:
        """``(W8, H8)``: a frame's copy-back raster, in blocks."""
        _, enc, _ = self.item
        if self.bsel is None:
            return enc["w"] // 8, enc["h"] // 8
        return 1, self.nb


class _Scratch:
    """The host buffer the stream is gathered into, kept between calls.

    A fresh buffer of 100 MB faults its pages in as it is first written,
    which costs more than the copy itself (PERF.md, "batched decode").  The
    buffer is reused by one call at a time, from the gather until the
    device has read it; a call that finds it taken allocates its own."""

    def __init__(self):
        self._lock = threading.Lock()
        self._buf = np.empty(0, dtype=np.int16)

    @contextlib.contextmanager
    def take(self, size: int):
        if not self._lock.acquire(blocking=False):
            yield np.empty(size, dtype=np.int16)
            return
        try:
            if self._buf.size < size:
                self._buf = np.empty(size, dtype=np.int16)
            yield self._buf[:size]
        finally:
            self._lock.release()


_SCRATCH = _Scratch()


def _gather(slots: list[_Slot], q: np.ndarray) -> None:
    """Fill ``q``, one group's ``[F, M, 64]`` int16 stream: each slot's
    selected GOPs, ROI blocks only, in its column span; zeros elsewhere."""
    end = slots[-1].offset + slots[-1].span
    q[:, end:] = 0                              # the padding columns
    for s in slots:
        _, enc, idx = s.item
        cols = q[:, s.offset:s.offset + s.span].reshape(
            q.shape[0], s.n_gops, s.nb, 64)
        cols[s.n:] = 0                           # the padding frames
        for j, g in enumerate(idx):
            rows = [enc["kq"][g]] + list(enc["pq"][g][:s.n - 1])
            for f, blocks in enumerate(rows):
                blocks = blocks.reshape(-1, 64)
                if s.bsel is None:
                    cols[f, j] = blocks
                else:
                    np.take(blocks, s.bsel, axis=0, out=cols[f, j],
                            mode="clip")


def _canvas(s: _Slot, planes: np.ndarray) -> tuple[np.ndarray, bool]:
    """One slot's output canvas from the copied-back planes (``ops``
    module doc), and whether it is a view of them."""
    _, enc, _ = s.item
    h, w = enc["h"], enc["w"]
    start = s.offset * 64
    gops = [planes[:s.n, start + g * s.nb * 64:start + (g + 1) * s.nb * 64]
            for g in range(s.n_gops)]
    if s.bsel is None:
        frames = [seg.reshape(s.n, h, w) for seg in gops]
        if len(frames) == 1:
            return frames[0], True
        return np.concatenate(frames), False
    canvas = np.zeros((s.n_gops * s.n, h, w), dtype=np.float32)
    view = canvas.reshape(-1, h // 8, 8, w // 8, 8)
    rs, cs = np.divmod(s.bsel, w // 8)
    for g, seg in enumerate(gops):
        # same advanced-index write as the oracle's ROI scatter
        view[g * s.n:(g + 1) * s.n, rs, :, cs] = \
            seg.reshape(s.n, s.nb, 8, 8).transpose(1, 0, 2, 3)
    return canvas, False


def _dispatch(q, tab, *, qp, use_pallas, interpret):
    """The flat stream -> the flat copied-back planes."""
    if decode_fused_op is ops.decode_fused_op:
        return decode_canvas_op(q, tab, qp=qp, use_pallas=use_pallas,
                                interpret=interpret)
    out = decode_fused_op(q.reshape(-1, tab.size, 8, 8), qp=qp,
                          use_pallas=use_pallas, interpret=interpret)
    return copy_back_op(out, tab)


def decode_tile_batch(items, *, use_pallas: bool | None = None,
                      interpret: bool = False) -> list[np.ndarray]:
    """Decode many tile selections with fused batched dispatches.

    ``items``: sequence of ``(enc, gop_indices, frames_within, blocks)``
    tuples.  Returns one ``[T', h, w] float32`` array per item, within
    :data:`ORACLE_ATOL` of ``decode_tile(enc, gop_indices, frames_within,
    blocks)``.  A full tile's array of one GOP is a read-only view of its
    dispatch's copied-back planes.
    """
    results: list = [None] * len(items)
    # (qp, F_bucket) -> next free column / that group's slots
    columns: dict[tuple[int, int], int] = {}
    slots_by_group: dict[tuple[int, int], list[_Slot]] = {}

    for i, (enc, gop_indices, frames_within, blocks) in enumerate(items):
        h, w, gop, qp = enc["h"], enc["w"], enc["gop"], enc["qp"]
        n_gops_total = len(enc["kq"])
        idx = (list(range(n_gops_total)) if gop_indices is None
               else list(gop_indices))
        n = gop if frames_within is None else max(1, min(frames_within, gop))
        nb_all = (h // 8) * (w // 8)
        bsel = None
        if blocks is not None:
            bsel = np.asarray(sorted(set(blocks)), dtype=np.intp)
            if bsel.size and not 0 <= bsel[0] <= bsel[-1] < nb_all:
                raise IndexError(f"block mask outside the {nb_all} blocks "
                                 "of the tile")
            if bsel.size == nb_all:
                bsel = None                    # a mask of every block
        nb = nb_all if bsel is None else int(bsel.size)
        if not idx or nb == 0:
            # nothing to dispatch: the oracle returns an all-zero canvas
            results[i] = np.zeros((len(idx) * n, h, w), dtype=np.float32)
            continue
        key = (qp, pad_bucket(n, lo=1))
        off = columns.get(key, 0)
        span = len(idx) * nb
        columns[key] = off + span
        slots_by_group.setdefault(key, []).append(
            _Slot((i, enc, idx), n, len(idx), bsel, nb, off, span))

    for (qp, f_bucket), slots in slots_by_group.items():
        m_pad = pad_bucket(columns[(qp, f_bucket)], lo=MIN_COLUMNS)
        with _SCRATCH.take(f_bucket * m_pad * 64) as q:
            with trace.span("tasm.decode.gather"):
                _gather(slots, q.reshape(f_bucket, m_pad, 64))
                tab = column_table(m_pad, [(s.offset, s.span, *s.raster())
                                           for s in slots])
            with trace.span("tasm.decode.dispatch"):
                out = _dispatch(q, tab, qp=qp, use_pallas=use_pallas,
                                interpret=interpret)
            with trace.span("tasm.decode.device"):
                out = jax.block_until_ready(out)
        with trace.span("tasm.decode.d2h"):
            out = np.asarray(out)
        n_views = 0
        with trace.span("tasm.decode.scatter"):
            planes = out.reshape(f_bucket, m_pad * 64)
            for s in slots:
                i, _, _ = s.item
                results[i], is_view = _canvas(s, planes)
                n_views += is_view
        trace.count("tasm.decode.h2d_bytes", q.nbytes + tab.nbytes)
        trace.count("tasm.decode.d2h_bytes", out.nbytes)
        trace.count("tasm.decode.view_slots", n_views)
        trace.count("tasm.decode.host_slots", len(slots) - n_views)
    return results
