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
flattened into a padded block stream:

1. **Gather** — for every item, the selected GOPs' coefficient blocks are
   gathered (ROI block masks applied *here*, on the host, so masked-out
   blocks never reach the accelerator) into columns of a ``[F, M, 8, 8]``
   int16 stream: row 0 the intra keyframe, rows 1..n-1 the inter residuals.
2. **Bucket** — items are grouped by ``(qp, F bucket)``; each group's
   stream is allocated at power-of-two column counts
   (:func:`repro.kernels.decode.ops.pad_bucket`) so jit traces stay bounded
   across arbitrary tile layouts.  Frame-depth padding appends zero
   coefficient rows, which decode to zero pixels *after* every real frame
   and are sliced off.
3. **Dispatch** — one fused dequant+IDCT+cumsum call per group: the Pallas
   kernel on TPU, the jitted jnp path under XLA elsewhere (both within
   :data:`ORACLE_ATOL` of numpy — see ``repro/kernels/decode``).
4. **Scatter** — each item's columns are scattered back into its output
   canvas exactly like the oracle (full tiles via the block-grid reshape,
   ROI masks via the same advanced-index write, unselected blocks zero).
"""
from __future__ import annotations

import jax
import numpy as np

from repro.kernels.decode.ops import MIN_COLUMNS, decode_fused_op, pad_bucket
from repro.utils import trace

#: max abs difference from the numpy oracle on 0-255 pixels (module doc)
ORACLE_ATOL = 1e-2

#: one decode request: (enc dict, gop_indices, frames_within, blocks) with
#: the exact semantics of ``decode_tile``'s parameters of the same names
DecodeItem = tuple


def _gather_gops(seq, idx: list[int]) -> np.ndarray:
    """Select GOP members from the ``kq``/``pq`` field, which is a stacked
    ndarray for in-memory tiles or a per-GOP list for lazy npz reads."""
    if isinstance(seq, np.ndarray):
        return seq[idx]
    return np.stack([seq[g] for g in idx])


class _Slot:
    """Where one item's columns live inside its group's block stream."""

    __slots__ = ("item", "n", "n_gops", "bsel", "offset", "span")

    def __init__(self, item, n, n_gops, bsel, offset, span):
        self.item = item
        self.n = n                  # frames decoded per selected GOP
        self.n_gops = n_gops
        self.bsel = bsel            # None = full tile
        self.offset = offset
        self.span = span


def _gather(slots: list[_Slot], f_bucket: int, m_pad: int) -> np.ndarray:
    """One group's ``[F, M, 8, 8]`` int16 block stream: each slot's
    selected GOPs, ROI blocks only, in its column span."""
    q = np.zeros((f_bucket, m_pad, 8, 8), dtype=np.int16)
    for s in slots:
        _, enc, idx = s.item
        kq = _gather_gops(enc["kq"], idx)          # [G, nb, 8, 8]
        if s.bsel is not None:
            kq = kq[:, s.bsel]
        q[0, s.offset:s.offset + s.span] = kq.reshape(-1, 8, 8)
        if s.n > 1:
            pq = _gather_gops(enc["pq"], idx)[:, :s.n - 1]
            if s.bsel is not None:
                pq = pq[:, :, s.bsel]
            # [G, n-1, nb, 8, 8] -> [n-1, G*nb, 8, 8] gop-major columns
            q[1:s.n, s.offset:s.offset + s.span] = \
                pq.transpose(1, 0, 2, 3, 4).reshape(s.n - 1, s.span, 8, 8)
    return q


def _scatter(s: _Slot, out: np.ndarray) -> np.ndarray:
    """One slot's columns of the decoded stream as its output canvas."""
    _, enc, _ = s.item
    h, w = enc["h"], enc["w"]
    seg = out[:s.n, s.offset:s.offset + s.span]
    if s.bsel is None:
        # [n, G, h/8, w/8, 8, 8] -> gop-major frames [G*n, h, w]
        arr = seg.reshape(s.n, s.n_gops, h // 8, w // 8, 8, 8)
        arr = arr.transpose(1, 0, 2, 4, 3, 5)
        return np.ascontiguousarray(arr.reshape(s.n_gops * s.n, h, w))
    canvas = np.zeros((s.n_gops * s.n, h, w), dtype=np.float32)
    view = canvas.reshape(-1, h // 8, 8, w // 8, 8)
    rs, cs = np.divmod(s.bsel, w // 8)
    frames = seg.reshape(s.n, s.n_gops, -1, 8, 8)
    frames = frames.transpose(1, 0, 2, 3, 4).reshape(
        s.n_gops * s.n, -1, 8, 8)
    # same advanced-index write as the oracle's ROI scatter
    view[:, rs, :, cs] = frames.transpose(1, 0, 2, 3)
    return canvas


def decode_tile_batch(items, *, use_pallas: bool | None = None,
                      interpret: bool = False) -> list[np.ndarray]:
    """Decode many tile selections with fused batched dispatches.

    ``items``: sequence of ``(enc, gop_indices, frames_within, blocks)``
    tuples.  Returns one ``[T', h, w] float32`` array per item, within
    :data:`ORACLE_ATOL` of ``decode_tile(enc, gop_indices, frames_within,
    blocks)``.
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
        if blocks is not None:
            bsel = np.asarray(sorted(set(blocks)), dtype=np.intp)
            nb_sel = int(bsel.size)
        else:
            bsel = None
            nb_sel = (h // 8) * (w // 8)
        if not idx or nb_sel == 0:
            # nothing to dispatch: the oracle returns an all-zero canvas
            results[i] = np.zeros((len(idx) * n, h, w), dtype=np.float32)
            continue
        key = (qp, pad_bucket(n, lo=1))
        off = columns.get(key, 0)
        span = len(idx) * nb_sel
        columns[key] = off + span
        slots_by_group.setdefault(key, []).append(
            _Slot((i, enc, idx), n, len(idx), bsel, off, span))

    for (qp, f_bucket), slots in slots_by_group.items():
        total = columns[(qp, f_bucket)]
        with trace.span("tasm.decode.gather"):
            q = _gather(slots, f_bucket, pad_bucket(total, lo=MIN_COLUMNS))
        with trace.span("tasm.decode.dispatch"):
            out = decode_fused_op(q, qp=qp, use_pallas=use_pallas,
                                  interpret=interpret)
        with trace.span("tasm.decode.device"):
            out = jax.block_until_ready(out)
        with trace.span("tasm.decode.d2h"):
            out = np.asarray(out)
        with trace.span("tasm.decode.scatter"):
            for s in slots:
                i, _, _ = s.item
                results[i] = _scatter(s, out)
    return results
