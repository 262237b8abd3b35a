"""Plain reference of what a selection returns, independent of the program.

The semantics a served selection must meet (the configuration's
``guarantees``): one region per detection of the label in the frame range,
cropped to its box, whose pixels are the codec's reconstruction of the
ingested frame.  The codec is fixed by its definition, restated here from
the specification and not imported: 8x8 orthonormal DCT-II blocks, a
JPEG-luminance quantization matrix scaled by ``qp / 16`` (intra), flattened
to ``max(0.75 m, 1)`` for P-frame residuals, GOPs whose first frame is
intra-coded and whose later frames code the residual against the previous
*reconstructed* frame (closed loop), every block coded on its own.  Because
every block is coded on its own, the reference encodes only the blocks that
the checked regions touch, and only up to the deepest frame they need, and
the tile layout the program chose does not enter the answer.

The encoder's arithmetic is float32 in the order the specification gives,
so its quantized coefficients are the program's, bit for bit; the decoder
reconstructs in float64.  ``decode`` also runs the two lower precisions that
the control uses: ``"high"`` (each float32 product split into three
bfloat16 products, XLA's ``Precision.HIGH``) and ``"bf16"`` (one).
"""
from __future__ import annotations

import functools

import ml_dtypes
import numpy as np

#: JPEG luminance base quantization matrix (ITU-T T.81, Annex K)
_BASE = np.array([
    [16, 11, 10, 16, 24, 40, 51, 61],
    [12, 12, 14, 19, 26, 58, 60, 55],
    [14, 13, 16, 24, 40, 57, 69, 56],
    [14, 17, 22, 29, 51, 87, 80, 62],
    [18, 22, 37, 56, 68, 109, 103, 77],
    [24, 35, 55, 64, 81, 104, 113, 92],
    [49, 64, 78, 87, 103, 121, 120, 101],
    [72, 92, 95, 98, 112, 100, 103, 99],
], dtype=np.float32)

PRECISIONS = ("f64", "f32", "high", "bf16")


@functools.lru_cache(maxsize=None)
def quant(qp: int, intra: bool) -> np.ndarray:
    m = _BASE * (max(qp, 1) / 16.0)
    if not intra:
        m = np.maximum(m * 0.75, 1.0)
    return np.maximum(m, 1.0).astype(np.float32)


@functools.lru_cache(maxsize=None)
def dct(n: int = 8) -> np.ndarray:
    """Orthonormal DCT-II basis [n, n], float32."""
    k = np.arange(n)[:, None]
    i = np.arange(n)[None, :]
    m = np.sqrt(2.0 / n) * np.cos(np.pi * (2 * i + 1) * k / (2 * n))
    m[0] = np.sqrt(1.0 / n)
    return m.astype(np.float32)


def frame_blocks(frame: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Blocks ``idx`` (row-major over the frame's 8x8 grid) -> [n, 8, 8]."""
    h, w = frame.shape
    grid = frame.reshape(h // 8, 8, w // 8, 8).swapaxes(1, 2)
    return grid.reshape(-1, 8, 8)[idx]


def _fwd(x: np.ndarray) -> np.ndarray:
    d = dct()
    return np.einsum("ij,njk,lk->nil", d, x, d, optimize=True)


def _inv(c: np.ndarray) -> np.ndarray:
    d = dct()
    return np.einsum("ji,njk,kl->nil", d, c, d, optimize=True)


def encode(frames: np.ndarray, qp: int) -> tuple[np.ndarray, np.ndarray]:
    """Closed-loop encode of one GOP's blocks.  ``frames``: [n, b, 8, 8]
    float32 pixels of ``b`` blocks over the GOP's first ``n`` frames.
    Returns (intra coefficients [b, 8, 8], residual coefficients
    [n-1, b, 8, 8]), int16."""
    mk, mp = quant(qp, True), quant(qp, False)
    kq = np.round(_fwd(frames[0]) / mk).astype(np.int16)
    recon = _inv(kq.astype(np.float32) * mk)
    pq = np.empty((len(frames) - 1,) + kq.shape, dtype=np.int16)
    for i in range(1, len(frames)):
        q = np.round(_fwd(frames[i] - recon) / mp).astype(np.int16)
        pq[i - 1] = q
        recon = recon + _inv(q.astype(np.float32) * mp)
    return kq, pq


#: a scaled coefficient this close to a half-integer rounds either way
#: depending on the last bit of a float32 sum, which the summation order
#: of the matrix library decides; such ties are the codec's to break
TIE = 1e-3
#: most tie decisions one block's search tries
MAX_TIES = 6
#: most distinct (frame, block)s one archive searches: a run whose answers
#: are wrong everywhere must still end promptly
MAX_SEARCHES = 256


def encode_block_ties(frames: np.ndarray, qp: int, depth: int
                      ) -> list[np.ndarray]:
    """The float64 reconstructions of one block at frame ``depth - 1``
    under every admissible encoding: every coefficient within ``TIE`` of a
    half-integer may round either way (in frame order, each choice changing
    the reconstruction that later frames are coded against).  ``frames``:
    [>= depth, 8, 8] float32 source pixels of the block."""
    mk, mp = quant(qp, True), quant(qp, False)
    out: list[np.ndarray] = []
    budget = [1 << MAX_TIES]

    def walk(i, recon, acc, ties):
        if budget[0] <= 0:
            return
        m = mk if i == 0 else mp
        x = frames[i][None] if i == 0 else (frames[i] - recon)[None]
        scaled = _fwd(x.astype(np.float32))[0] / m
        q = np.round(scaled)
        frac = np.abs(scaled - np.trunc(scaled))
        amb = np.flatnonzero((np.abs(frac - 0.5) < TIE).ravel())
        choices = [q]
        if ties < MAX_TIES:
            for j in amb:
                alt = q.copy()
                alt.flat[j] += 1.0 if alt.flat[j] <= scaled.flat[j] else -1.0
                choices.append(alt)
        for c in choices:
            cq = c.astype(np.int16).astype(np.float32)
            step = _inv((cq * m)[None])[0]
            rec = step if i == 0 else recon + step
            dec = (cq.astype(np.float64) * m)
            dstep = dct().T.astype(np.float64) @ dec @ dct().astype(
                np.float64)
            a = dstep if i == 0 else acc + dstep
            if i == depth - 1:
                budget[0] -= 1
                out.append(a)
            else:
                walk(i + 1, rec, a, ties + (c is not q))

    walk(0, None, None, 0)
    return out


def _split_bf16(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def _matmul(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    if precision == "f64":
        return a.astype(np.float64) @ b.astype(np.float64)
    if precision == "f32":
        return a.astype(np.float32) @ b.astype(np.float32)
    ahi, alo = _split_bf16(a.astype(np.float32))
    bhi, blo = _split_bf16(b.astype(np.float32))
    if precision == "bf16":
        return ahi @ bhi
    if precision == "high":
        return ahi @ bhi + (ahi @ blo + alo @ bhi)
    raise ValueError(f"unknown precision {precision!r}; want {PRECISIONS}")


def decode(kq: np.ndarray, pq: np.ndarray, qp: int, depth: int,
           precision: str = "f64") -> np.ndarray:
    """Reconstruct the first ``depth`` frames of the coded blocks:
    [depth, b, 8, 8], float64 or float32 by ``precision``."""
    d = dct()
    coeffs = np.concatenate([(kq.astype(np.float32) * quant(qp, True))[None],
                             pq[:depth - 1].astype(np.float32)
                             * quant(qp, False)], axis=0)
    x = _matmul(d.T, coeffs, precision)          # D^T C
    x = _matmul(x, d, precision)                 # ... D
    dtype = np.float64 if precision == "f64" else np.float32
    return np.cumsum(x.astype(dtype), axis=0, dtype=dtype)


class Archive:
    """The reference's view of one ingested camera: the source frames and
    detections, coded block by block as the checks need them."""

    def __init__(self, frames: np.ndarray, detections, gop: int, qp: int):
        self.frames = frames
        self.detections = detections
        self.gop, self.qp = gop, qp
        self.h, self.w = frames.shape[1:]
        #: gop -> (block -> column, intra [b, 8, 8], residual [n-1, b, 8, 8])
        self._coded: dict[int, tuple] = {}
        self._decoded: dict[tuple[int, str], np.ndarray] = {}
        #: (frame, block row, block col) -> its admissible reconstructions
        self._ties: dict[tuple[int, int, int], list] = {}
        self.searches_left = MAX_SEARCHES

    def regions(self, label: str, lo: int, hi: int) -> list:
        """The region keys a selection must return: sorted ``(frame,
        box)`` of every ``label`` detection in frames [lo, hi)."""
        return sorted((f, tuple(box))
                      for f in range(max(lo, 0), min(hi, len(self.frames)))
                      for lab, box in self.detections[f] if lab == label)

    def _blocks(self, box) -> np.ndarray:
        y1, x1, y2, x2 = box
        rows = np.arange(y1 // 8, (y2 + 7) // 8)
        cols = np.arange(x1 // 8, (x2 + 7) // 8)
        return (rows[:, None] * (self.w // 8) + cols[None, :]).ravel()

    def prepare(self, keys) -> None:
        """Code every block that the regions ``keys`` touch, each GOP up to
        the deepest frame asked of it.  Call once, with every key."""
        need: dict[int, tuple[set, int]] = {}
        for f, box in keys:
            g = f // self.gop
            blocks, depth = need.get(g, (set(), 0))
            blocks.update(self._blocks(box).tolist())
            need[g] = (blocks, max(depth, f - g * self.gop + 1))
        self._coded.clear()
        self._decoded.clear()
        self._ties.clear()
        for g, (blocks, depth) in need.items():
            idx = np.array(sorted(blocks), dtype=np.intp)
            f0 = g * self.gop
            px = np.stack([frame_blocks(self.frames[f0 + i], idx)
                           for i in range(depth)])
            kq, pq = encode(px.astype(np.float32), self.qp)
            col = {b: j for j, b in enumerate(idx.tolist())}
            self._coded[g] = (col, kq, pq)

    def pixels(self, frame: int, box, precision: str = "f64") -> np.ndarray:
        """The region's reconstructed pixels (``prepare`` first)."""
        g, rel = frame // self.gop, frame % self.gop
        col, kq, pq = self._coded[g]
        rec = self._decoded.get((g, precision))
        if rec is None:
            rec = decode(kq, pq, self.qp, len(pq) + 1, precision)
            self._decoded[(g, precision)] = rec
        y1, x1, y2, x2 = box
        cols = [col[b] for b in self._blocks(box).tolist()]
        nr, nc = (y2 + 7) // 8 - y1 // 8, (x2 + 7) // 8 - x1 // 8
        canvas = rec[rel, cols].reshape(nr, nc, 8, 8).swapaxes(1, 2).reshape(
            nr * 8, nc * 8)
        oy, ox = y1 % 8, x1 % 8
        return canvas[oy:oy + y2 - y1, ox:ox + x2 - x1]

    def gap(self, frame: int, box, served: np.ndarray, limit: float
            ) -> tuple[float, int]:
        """The widest gap between ``served`` and the region's
        reconstruction, and how many of its blocks needed a tie broken the
        other way to come within ``limit`` (``prepare`` first)."""
        ref = self.pixels(frame, box)
        err = np.abs(served - ref)
        if not err.size or err.max() <= limit:
            return (float(err.max()) if err.size else 0.0), 0
        g, rel = frame // self.gop, frame % self.gop
        f0 = g * self.gop
        y1, x1, y2, x2 = box
        worst, ties = 0.0, 0
        for r in range(y1 // 8, (y2 + 7) // 8):
            for c in range(x1 // 8, (x2 + 7) // 8):
                ys = slice(max(y1, r * 8) - y1, min(y2, r * 8 + 8) - y1)
                xs = slice(max(x1, c * 8) - x1, min(x2, c * 8 + 8) - x1)
                e = float(err[ys, xs].max())
                cands = self._ties.get((frame, r, c))
                if e > limit and cands is None and self.searches_left > 0:
                    self.searches_left -= 1
                    cands = encode_block_ties(
                        self.frames[f0:f0 + rel + 1, r * 8:r * 8 + 8,
                                    c * 8:c * 8 + 8], self.qp, rel + 1)
                    self._ties[(frame, r, c)] = cands
                if e > limit and cands is not None:
                    where = (slice(ys.start + y1 - r * 8, ys.stop + y1 - r * 8),
                             slice(xs.start + x1 - c * 8, xs.stop + x1 - c * 8))
                    e = min(float(np.max(np.abs(a[where] - served[ys, xs])))
                            for a in cands)
                    ties += e <= limit
                worst = max(worst, e)
        return worst, ties
