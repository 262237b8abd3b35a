"""The reference and its control, against the program's own codec.

The reference imports nothing of the program; these tests do, to show that
its encoder gives the program's quantized coefficients bit for bit on any
tile of the frame, and that its control -- the decode at XLA's ``HIGH``
precision, one step below the ``HIGHEST`` the configurations state --
misses the pixel-gap limit that the program's own float32 decode meets.
"""
from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import corpus  # noqa: E402
import reference  # noqa: E402


def _small(name: str, seed: int):
    import run

    cfg = run.rehearsal_size(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))
    frames, dets = corpus.generate(cfg, seed)
    return cfg, frames, dets


@pytest.mark.parametrize("rect", [(0, 0, 96, 160), (8, 16, 72, 96),
                                  (32, 32, 96, 160), (88, 152, 96, 160)])
def test_encode_matches_program_bit_for_bit(rect):
    from repro.codec.encode import EncoderConfig, decode_tile, encode_tile

    cfg, frames, _ = _small("visualroad-2k-kqko", 5)
    y1, x1, y2, x2 = rect
    enc = encode_tile(np.ascontiguousarray(frames[:, y1:y2, x1:x2]),
                      EncoderConfig(gop=cfg["gop"], qp=cfg["qp"]))
    rows, cols = np.arange(y1 // 8, y2 // 8), np.arange(x1 // 8, x2 // 8)
    idx = (rows[:, None] * (cfg["width"] // 8) + cols[None, :]).ravel()
    for g in range(cfg["n_frames"] // cfg["gop"]):
        f0 = g * cfg["gop"]
        px = np.stack([reference.frame_blocks(frames[f0 + i], idx)
                       for i in range(cfg["gop"])])
        kq, pq = reference.encode(px, cfg["qp"])
        assert np.array_equal(kq, enc["kq"][g])
        assert np.array_equal(pq, enc["pq"][g])
    arch = reference.Archive(frames, [[]] * len(frames), cfg["gop"],
                             cfg["qp"])
    keys = [(f, rect) for f in range(len(frames))]
    arch.prepare(keys)
    oracle = decode_tile(enc)
    for f, box in keys:
        assert np.array_equal(arch.pixels(f, box, "f32"), oracle[f])
        assert np.max(np.abs(arch.pixels(f, box) - oracle[f])) < 1e-3


@pytest.mark.parametrize("name", ["visualroad-2k-kqko",
                                  "mot16-1080p-served"])
def test_control_fails_the_limit(name):
    """The control: the reference's decode at HIGH precision reads a gap
    above the configuration's limit on every seed; float32 stays below."""
    for seed in (1, 2, 3):
        cfg, frames, dets = _small(name, seed)
        arch = reference.Archive(frames, dets, cfg["gop"], cfg["qp"])
        keys = sorted({(f, box) for f, d in enumerate(dets)
                       for _, box in d})
        arch.prepare(keys)

        def gap(precision):
            return max(float(np.max(np.abs(arch.pixels(f, b, precision)
                                           - arch.pixels(f, b))))
                       for f, b in keys)

        limit = cfg["check"]["pixel_gap_limit"]
        assert gap("f32") < limit / 3
        assert gap("high") > limit
        assert gap("bf16") > 100 * limit


def test_regions_are_every_detection_of_the_label():
    cfg, frames, dets = _small("mot16-1080p-served", 4)
    arch = reference.Archive(frames, dets, cfg["gop"], cfg["qp"])
    want = arch.regions("person", 3, 9)
    n = sum(1 for f in range(3, 9) for lab, _ in dets[f] if lab == "person")
    assert len(want) == n and all(3 <= f < 9 for f, _ in want)


def _encode_with_flip(blk, qp, at_frame, at_coef):
    """The codec's closed-loop encode of one block, with the rounding of
    one coefficient at one frame broken the other way."""
    mk, mp = reference.quant(qp, True), reference.quant(qp, False)
    qs, recon = [], None
    for i in range(len(blk)):
        m = mk if i == 0 else mp
        x = blk[i] if i == 0 else blk[i] - recon
        s = reference._fwd(x[None].astype(np.float32))[0] / m
        q = np.round(s)
        if i == at_frame:
            q.flat[at_coef] += 1.0 if q.flat[at_coef] <= s.flat[at_coef] \
                else -1.0
        qs.append(q.astype(np.int16))
        step = reference._inv((q.astype(np.float32) * m)[None])[0]
        recon = step if i == 0 else recon + step
    return qs[0][None], np.stack(qs[1:])[:, None]


def test_a_tie_broken_the_other_way_is_admissible_and_nothing_else():
    """A block whose encoder rounded a near-half coefficient the other way
    passes; a block altered by as little as 0.01 does not."""
    cfg, frames, dets = _small("mot16-1080p-served", 6)
    qp, limit = cfg["qp"], cfg["check"]["pixel_gap_limit"]
    found = None
    for r in range(cfg["height"] // 8):
        for c in range(cfg["width"] // 8):
            blk = frames[:30, r * 8:r * 8 + 8, c * 8:c * 8 + 8]
            mk, mp = reference.quant(qp, True), reference.quant(qp, False)
            recon = None
            for i in range(1, 30):
                kq, pq = reference.encode(blk[:i + 1, None], qp)
                recon = reference.decode(kq, pq, qp, i, "f32")[i - 1, 0]
                s = reference._fwd((blk[i] - recon)[None])[0] / mp
                near = np.flatnonzero(np.abs(np.abs(s - np.trunc(s)) - 0.5)
                                      < reference.TIE / 4)
                if near.size:
                    found = (r, c, i, int(near[0]))
                    break
            if found:
                break
        if found:
            break
    assert found, "no near-tie coefficient in the test archive"
    r, c, i, j = found
    frame, box = i, (r * 8, c * 8, r * 8 + 8, c * 8 + 8)
    arch = reference.Archive(frames, dets, cfg["gop"], qp)
    arch.prepare([(frame, box)])
    kq, pq = _encode_with_flip(frames[:30, r * 8:r * 8 + 8, c * 8:c * 8 + 8],
                               qp, i, j)
    served = reference.decode(kq, pq, qp, frame + 1, "f32")[frame, 0]
    plain = np.max(np.abs(served - arch.pixels(frame, box)))
    g, ties = arch.gap(frame, box, served, limit)
    assert g <= limit and ties == 1, (plain, g)
    g, ties = arch.gap(frame, box, served + np.float32(0.01), limit)
    assert g > limit and ties == 0
