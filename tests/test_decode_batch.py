"""Batched fused decode: the "batched" backend against the numpy float32
oracle — the kernel batch op, ``decode_tile_batch``, and every engine path
that can reach ``TileStore.decode_tiles`` (serial scans, merged
``execute_many`` batches, serve sessions, mid-batch retiles).

Across backends the contract is a tolerance, ``ORACLE_ATOL`` = 1e-2 max abs
on 0-255 pixels, not bit-identity: the accelerator accumulates each 8x8
contraction and the closed-loop sum over up to 32 frames of a GOP in f32,
in its own order, so results differ from numpy's by f32 rounding (about
1e-4 here on XLA CPU).  A bf16-precision contraction — a TPU's default for
f32 matmuls — misses by far more, and a case below shows the tolerance
catches it.  Within one backend the arithmetic is deterministic, so cache
on vs off, serial vs merged batches, and the decode counters stay exact.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.codec.batch import ORACLE_ATOL, decode_tile_batch
from repro.codec.encode import EncoderConfig, decode_tile, encode_tile
from repro.codec.quant import quant_matrix
from repro.codec.transform import dct_matrix
from repro.core import (CacheConfig, DecodeConfig, NoTilingPolicy,
                        RegretPolicy, VideoStore, uniform_layout)
from repro.core.cost import CostModel
from repro.core.storage import TileStore
from repro.kernels.decode import (MIN_COLUMNS, decode_fused_op,
                                  decode_fused_ref, pad_bucket)

ENC = EncoderConfig(gop=16, qp=8)
MODEL = CostModel(beta=1.4e-8, gamma=1e-5)
MODEL.encode_per_pixel = 3.4e-8
MODEL.encode_per_tile = 1e-4


def fill(store, name, frames, dets, policy=None):
    store.add_video(name, encoder=ENC, policy=policy or NoTilingPolicy(),
                    cost_model=MODEL)
    store.ingest(name, frames)
    store.add_detections(name, {f: d for f, d in enumerate(dets)})


def assert_regions_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra[:-1] == rb[:-1]
        np.testing.assert_array_equal(ra[-1], rb[-1])


def assert_close(got, want):
    """Cross-backend contract: same dtype and shape, pixels within
    ``ORACLE_ATOL`` of the oracle."""
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0, atol=ORACLE_ATOL)


def assert_regions_close(a, b):
    """Region keys (frame, box) equal exactly; pixels within tolerance."""
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra[:-1] == rb[:-1]
        assert_close(ra[-1], rb[-1])


# ------------------------------------------------------------- pad_bucket
@settings(max_examples=60, deadline=None)
@given(st.integers(1, 1 << 16), st.sampled_from([1, 8, 64]))
def test_pad_bucket_properties(n, lo):
    b = pad_bucket(n, lo)
    assert b >= n and b >= lo
    assert b & (b - 1) == 0 or b == lo  # power of two (or the floor)
    assert pad_bucket(b, lo) == b       # idempotent
    if n > lo:
        assert b < 2 * n                # never more than one octave up


def test_pad_bucket_bounds_trace_count():
    # any workload's distinct padded sizes grow logarithmically
    sizes = {pad_bucket(n, MIN_COLUMNS) for n in range(1, 5000)}
    assert len(sizes) <= 8


# ----------------------------------------------- decode_tile_batch oracle
def _rand_enc(rng, h, w, gop, qp, n_gops):
    frames = (rng.random((n_gops * gop, h, w), dtype=np.float32) * 255.0)
    return encode_tile(frames, EncoderConfig(gop=gop, qp=qp))


layout_st = st.tuples(st.integers(1, 4), st.integers(1, 4),
                      st.integers(1, 3), st.sampled_from([4, 8]),
                      st.sampled_from([4, 8, 12]))


@settings(max_examples=12, deadline=None)
@given(st.lists(layout_st, min_size=1, max_size=6), st.integers(0, 999))
def test_batch_bit_identical_to_decode_tile(specs, seed):
    rng = np.random.default_rng(seed)
    items = []
    for bh, bw, n_gops, gop, qp in specs:
        h, w = bh * 8, bw * 8
        enc = _rand_enc(rng, h, w, gop, qp, n_gops)
        # random GOP subset, tail depth, and ROI mask (sometimes full)
        gsel = sorted(rng.choice(n_gops, size=rng.integers(1, n_gops + 1),
                                 replace=False).tolist())
        fw = (None if rng.random() < 0.5
              else int(rng.integers(1, gop + 1)))
        nb = bh * bw
        roll = rng.random()
        if roll < 0.4:
            blocks = None                        # full tile
        elif roll < 0.5:
            blocks = tuple(range(nb))            # mask == every block
        else:
            k = int(rng.integers(1, nb + 1))
            blocks = tuple(sorted(
                rng.choice(nb, size=k, replace=False).tolist()))
        items.append((enc, gsel, fw, blocks))
    got = decode_tile_batch(items)
    for (enc, gsel, fw, blocks), arr in zip(items, got):
        want = decode_tile(enc, gop_indices=gsel, frames_within=fw,
                           blocks=blocks)
        assert_close(arr, want)


class TestDecodeTileBatchOracle:
    def test_pallas_interpret_matches_oracle(self):
        # the TPU kernel path, interpreted on CPU: same contract
        rng = np.random.default_rng(7)
        items = []
        for bh, bw, n_gops in [(1, 1, 1), (2, 3, 2), (4, 2, 1)]:
            enc = _rand_enc(rng, bh * 8, bw * 8, 8, 8, n_gops)
            items.append((enc, list(range(n_gops)), None, None))
        items.append((items[1][0], [0], 3, (0, 2, 5)))
        got = decode_tile_batch(items, use_pallas=True, interpret=True)
        for (enc, gsel, fw, blocks), arr in zip(items, got):
            assert_close(arr, decode_tile(enc, gop_indices=gsel,
                                          frames_within=fw, blocks=blocks))

    def test_degenerate_items(self):
        rng = np.random.default_rng(3)
        enc = _rand_enc(rng, 16, 16, 4, 8, 2)
        got = decode_tile_batch([
            (enc, [], None, None),          # no GOPs selected
            (enc, [0], None, ()),           # empty ROI mask
            (enc, [0, 1], 1, None),         # single-frame prefix
        ])
        assert got[0].shape == (0, 16, 16)
        # an empty mask dispatches nothing: exact zeros, like the oracle
        np.testing.assert_array_equal(
            got[1], decode_tile(enc, gop_indices=[0], blocks=()))
        assert_close(got[2],
                     decode_tile(enc, gop_indices=[0, 1], frames_within=1))


# ------------------------------------------------ TileStore backend parity
class TestStoreBackends:
    def _pair(self, frames, layout=None):
        stores = []
        for backend in ("numpy", "batched"):
            ts = TileStore("v", ENC, sot_len=32, decode_backend=backend)
            ts.ingest(frames)
            if layout is not None:
                ts.retile(0, layout)
            stores.append(ts)
        return stores

    def test_decode_tiles_identical_with_depths_and_masks(self, small_video):
        frames, _ = small_video
        H, W = frames.shape[1:]
        a, b = self._pair(frames, uniform_layout(H, W, 3, 4))
        base_a, base_b = a.tiles_decoded_total, b.tiles_decoded_total
        depths = {0: 5, 1: 16, 2: 32, 5: 23, 11: 1}
        masks = {0: (0, 1, 7), 2: None, 5: tuple(range(10))}
        tiles = sorted(depths)
        da = a.decode_tiles(0, tiles, n_frames=depths, blocks=masks)
        db = b.decode_tiles(0, tiles, n_frames=depths, blocks=masks)
        assert sorted(da) == sorted(db) == tiles
        for t in tiles:
            assert da[t].shape[0] == depths[t]
            assert_close(db[t], da[t])
        assert (a.tiles_decoded_total - base_a ==
                b.tiles_decoded_total - base_b == len(tiles))
        assert a.pixels_decoded_total == b.pixels_decoded_total

    def test_full_sot_roundtrip_identical(self, small_video):
        frames, _ = small_video
        H, W = frames.shape[1:]
        a, b = self._pair(frames, uniform_layout(H, W, 2, 2))
        assert_close(b.decode_full_sot(0), a.decode_full_sot(0))

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="decode_backend"):
            TileStore("v", ENC, decode_backend="cuda")
        with pytest.raises(ValueError, match="decode_backend"):
            VideoStore(decode_backend="cuda")

    def test_env_override_selects_backend(self, monkeypatch):
        monkeypatch.setenv("REPRO_DECODE_BACKEND", "batched")
        assert VideoStore().decode_backend == "batched"
        # an explicit argument wins over the environment
        assert VideoStore(decode_backend="numpy").decode_backend == "numpy"


# ----------------------------------------------- engine paths, both backends
def _pair_stores(frames, dets, *, policy=None, **kw):
    out = []
    for backend in ("numpy", "batched"):
        s = VideoStore(decode_backend=backend, **kw)
        fill(s, "cam0", frames, dets,
             policy=policy() if policy else None)
        out.append(s)
    return out


class TestEngineBackendParity:
    def test_serial_scans_identical(self, small_video):
        frames, dets = small_video
        H, W = frames.shape[1:]
        a, b = _pair_stores(frames, dets)
        for s in (a, b):
            s.retile("cam0", 0, uniform_layout(H, W, 3, 4))
        queries = [("car", (0, 32)), ("person", (3, 21)), ("car", (10, 11))]
        for lbl, fr in queries:
            ra = a.scan("cam0").labels(lbl).frames(*fr).execute()
            rb = b.scan("cam0").labels(lbl).frames(*fr).execute()
            assert_regions_close(ra.regions, rb.regions)
            assert ra.stats.pixels_decoded == rb.stats.pixels_decoded
            assert ra.stats.tiles_fetched == rb.stats.tiles_fetched
        sa, sb = a.video("cam0").store, b.video("cam0").store
        assert sa.tiles_decoded_total == sb.tiles_decoded_total
        assert sa.pixels_decoded_total == sb.pixels_decoded_total

    def test_execute_many_merged_batch_identical(self, small_video):
        frames, dets = small_video
        H, W = frames.shape[1:]
        a, b = _pair_stores(frames, dets)
        for s in (a, b):
            s.retile("cam0", 0, uniform_layout(H, W, 2, 3))
        queries = [("car", (0, 32)), ("car", (0, 5)), ("person", (8, 30)),
                   ("car", (12, 19))]
        ra = a.execute_many(
            [a.scan("cam0").labels(l).frames(*fr) for l, fr in queries])
        rb = b.execute_many(
            [b.scan("cam0").labels(l).frames(*fr) for l, fr in queries])
        for x, y in zip(ra, rb):
            assert_regions_close(x.regions, y.regions)
            assert x.stats.cache_misses == y.stats.cache_misses
        sa, sb = a.video("cam0").store, b.video("cam0").store
        assert sa.tiles_decoded_total == sb.tiles_decoded_total
        assert sa.pixels_decoded_total == sb.pixels_decoded_total

    def test_mid_batch_retile_identical(self, small_video):
        frames, dets = small_video
        a, b = _pair_stores(frames, dets, policy=RegretPolicy,
                            tuning="inline", tile_cache_bytes=0)
        n = 10  # enough repeats to push RegretPolicy over its threshold
        ra = a.execute_many(
            [a.scan("cam0").labels("car").frames(0, 32) for _ in range(n)])
        rb = b.execute_many(
            [b.scan("cam0").labels("car").frames(0, 32) for _ in range(n)])
        assert any(r.stats.retile_s > 0 for r in ra)  # it retiled
        for x, y in zip(ra, rb):
            assert_regions_close(x.regions, y.regions)
        layouts = lambda s: [(r.layout, r.epoch)
                             for r in s.video("cam0").store.sots]
        assert layouts(a) == layouts(b)

    def test_serve_session_identical(self, small_video):
        frames, dets = small_video
        a, b = _pair_stores(frames, dets)
        results = []
        for s in (a, b):
            with s.serve() as session:
                futs = [session.submit(
                    s.scan("cam0").labels("car").frames(0, 32))
                    for _ in range(6)]
                results.append([f.result(timeout=60) for f in futs])
        for x, y in zip(*results):
            assert_regions_close(x.regions, y.regions)
        sa, sb = a.video("cam0").store, b.video("cam0").store
        assert sa.tiles_decoded_total == sb.tiles_decoded_total
        assert sa.pixels_decoded_total == sb.pixels_decoded_total


# -------------------------------------------- tolerance vs precision
def _gop_stream(seed: int, gop: int = 32, side: int = 32):
    """One random GOP as a kernel block stream ``[gop, nb, 8, 8]`` and the
    oracle's reconstruction of it in the same block order."""
    enc = _rand_enc(np.random.default_rng(seed), side, side, gop, 8, 1)
    q = np.concatenate([enc["kq"][0][None], enc["pq"][0]], axis=0)
    frames = decode_tile(enc)
    nb = side // 8
    want = frames.reshape(gop, nb, 8, nb, 8).transpose(0, 1, 3, 2, 4)
    return q, want.reshape(gop, nb * nb, 8, 8)


def _decode_bf16(q: np.ndarray, qp: int) -> np.ndarray:
    """``decode_fused_ref`` with each contraction's operands rounded to
    bf16 and accumulated in f32: what a TPU's DEFAULT precision does to an
    f32 matmul."""
    bf = jnp.bfloat16
    d = jnp.asarray(dct_matrix()).astype(bf)
    scale = np.concatenate([quant_matrix(qp, True)[None],
                            np.broadcast_to(quant_matrix(qp, False),
                                            (q.shape[0] - 1, 8, 8))])
    c = jnp.asarray(q.astype(np.float32) * scale[:, None]).astype(bf)
    x = jnp.einsum("ji,fnjk->fnik", d, c, preferred_element_type=jnp.float32)
    x = jnp.einsum("fnik,kl->fnil", x.astype(bf), d,
                   preferred_element_type=jnp.float32)
    return np.cumsum(np.asarray(x), axis=0, dtype=np.float32)


@pytest.mark.parametrize("seed", [0, 1])
def test_tolerance_separates_f32_from_bf16(seed):
    q, want = _gop_stream(seed)
    f32 = np.asarray(decode_fused_ref(jnp.asarray(q), 8))
    assert np.abs(f32 - want).max() <= ORACLE_ATOL
    bf16 = _decode_bf16(q, 8)
    assert np.abs(bf16 - want).max() > ORACLE_ATOL


# ------------------------------- within the batched backend: exact
def _batched_store(frames, dets, **kw):
    s = VideoStore(decode=DecodeConfig(backend="batched"), **kw)
    fill(s, "cam0", frames, dets)
    H, W = frames.shape[1:]
    s.retile("cam0", 0, uniform_layout(H, W, 2, 3))
    return s


QUERIES = [("car", (0, 32)), ("car", (0, 5)), ("person", (3, 21)),
           ("car", (12, 19))]


class TestBatchedDeterminism:
    def test_cache_on_off_identical(self, small_video):
        frames, dets = small_video
        cached = _batched_store(frames, dets)
        uncached = _batched_store(frames, dets,
                                  cache=CacheConfig(budget_bytes=0))
        for lbl, fr in QUERIES:
            rc = cached.scan("cam0").labels(lbl).frames(*fr).execute()
            ru = uncached.scan("cam0").labels(lbl).frames(*fr).execute()
            assert_regions_equal(rc.regions, ru.regions)
        assert cached.stats()["cache"]["hits"] > 0

    def test_serial_vs_merged_identical(self, small_video):
        frames, dets = small_video
        serial = _batched_store(frames, dets)
        merged = _batched_store(frames, dets)
        rs = [serial.scan("cam0").labels(l).frames(*fr).execute()
              for l, fr in QUERIES]
        rm = merged.execute_many(
            [merged.scan("cam0").labels(l).frames(*fr) for l, fr in QUERIES])
        for x, y in zip(rs, rm):
            assert_regions_equal(x.regions, y.regions)


# ------------ the copy back in canvas order against the host relayout
def _ref_gather_gops(seq, idx):
    if isinstance(seq, np.ndarray):
        return seq[idx]
    return np.stack([seq[g] for g in idx])


def _ref_gather(slots, f_bucket, m_pad):
    """The ``[F, M, 8, 8]`` stream built into zeros by fancy-index copies,
    as the host built it before: slots are ``(enc, idx, n, bsel, offset,
    span)``."""
    q = np.zeros((f_bucket, m_pad, 8, 8), dtype=np.int16)
    for enc, idx, n, bsel, off, span in slots:
        kq = _ref_gather_gops(enc["kq"], idx)
        if bsel is not None:
            kq = kq[:, bsel]
        q[0, off:off + span] = kq.reshape(-1, 8, 8)
        if n > 1:
            pq = _ref_gather_gops(enc["pq"], idx)[:, :n - 1]
            if bsel is not None:
                pq = pq[:, :, bsel]
            q[1:n, off:off + span] = \
                pq.transpose(1, 0, 2, 3, 4).reshape(n - 1, span, 8, 8)
    return q


def _ref_scatter(slot, out):
    """One slot's columns of the decoded stream as its canvas, on the
    host, as before the device copied back in canvas order."""
    enc, idx, n, bsel, off, span = slot
    h, w, g = enc["h"], enc["w"], len(idx)
    seg = out[:n, off:off + span]
    if bsel is None:
        arr = seg.reshape(n, g, h // 8, w // 8, 8, 8)
        arr = arr.transpose(1, 0, 2, 4, 3, 5)
        return np.ascontiguousarray(arr.reshape(g * n, h, w))
    canvas = np.zeros((g * n, h, w), dtype=np.float32)
    view = canvas.reshape(-1, h // 8, 8, w // 8, 8)
    rs, cs = np.divmod(bsel, w // 8)
    frames = seg.reshape(n, g, -1, 8, 8).transpose(1, 0, 2, 3, 4)
    view[:, rs, :, cs] = frames.reshape(g * n, -1, 8, 8).transpose(1, 0, 2, 3)
    return canvas


def _ref_decode_tile_batch(items, use_pallas, interpret):
    """``decode_tile_batch`` as it was: stream gathered on the host,
    decoded by ``decode_fused_op``, canvases scattered on the host."""
    results = [None] * len(items)
    columns, groups = {}, {}
    for i, (enc, gop_indices, frames_within, blocks) in enumerate(items):
        h, w, gop, qp = enc["h"], enc["w"], enc["gop"], enc["qp"]
        idx = (list(range(len(enc["kq"]))) if gop_indices is None
               else list(gop_indices))
        n = gop if frames_within is None else max(1, min(frames_within, gop))
        bsel = (None if blocks is None
                else np.asarray(sorted(set(blocks)), dtype=np.intp))
        nb = (h // 8) * (w // 8) if bsel is None else bsel.size
        if not idx or nb == 0:
            results[i] = np.zeros((len(idx) * n, h, w), dtype=np.float32)
            continue
        key = (qp, pad_bucket(n, lo=1))
        off = columns.get(key, 0)
        columns[key] = off + len(idx) * nb
        groups.setdefault(key, []).append(
            (i, (enc, idx, n, bsel, off, len(idx) * nb)))
    for (qp, f_bucket), slots in groups.items():
        q = _ref_gather([s for _, s in slots], f_bucket,
                        pad_bucket(columns[(qp, f_bucket)], lo=MIN_COLUMNS))
        out = np.asarray(decode_fused_op(q, qp=qp, use_pallas=use_pallas,
                                         interpret=interpret))
        for i, s in slots:
            results[i] = _ref_scatter(s, out)
    return results


def _layout_items(case):
    """Selections of one kind: tiles of 1x1 to 3x4 blocks, GOP 8."""
    rng = np.random.default_rng(sum(map(ord, case)))
    enc = {(bh, bw, qp): _rand_enc(rng, bh * 8, bw * 8, 8, qp, 3)
           for bh, bw in [(1, 1), (2, 3), (3, 2), (3, 4)] for qp in (8, 12)}
    tiles = [enc[(bh, bw, 8)] for bh, bw in [(2, 3), (1, 1), (3, 4)]]
    if case == "full_tiles":
        return [(e, [1], None, None) for e in tiles]
    if case == "roi_masks":
        return [(tiles[0], [0], None, (0, 2, 5)),
                (tiles[2], [2], None, (11, 3, 4, 7)),
                (enc[(3, 2, 8)], [1], None, (1,))]
    if case == "all_block_masks":
        return [(e, [0], None, tuple(range((e["h"] // 8) * (e["w"] // 8))))
                for e in tiles]
    if case == "multi_gop":
        return [(tiles[0], [0, 1, 2], None, None),
                (tiles[2], [2, 0], None, (1, 6, 9)),
                (tiles[1], None, None, None)]
    if case == "tails":
        return [(tiles[0], [2], 3, None), (tiles[2], [1], 5, (0, 4, 8)),
                (enc[(3, 2, 8)], [0, 1], 1, None),
                (tiles[1], [0], 8, (0,))]
    if case == "two_qp_groups":
        return [(tiles[0], [0], None, None),
                (enc[(3, 4, 12)], [1], None, (2, 3)),
                (enc[(2, 3, 12)], [0, 2], 6, None),
                (tiles[2], [1], 2, (5,))]
    assert case == "empty_selection"
    return [(tiles[0], [], None, None), (tiles[2], [1], None, ()),
            (tiles[1], [0], 4, None)]


LAYOUT_CASES = ["full_tiles", "roi_masks", "all_block_masks", "multi_gop",
                "tails", "two_qp_groups", "empty_selection"]
BACKENDS = [pytest.param(False, id="jnp"),
            pytest.param(True, id="pallas_interpret")]


@pytest.mark.parametrize("use_pallas", BACKENDS)
@pytest.mark.parametrize("case", LAYOUT_CASES)
def test_canvases_bitwise_equal_to_host_relayout(case, use_pallas):
    items = _layout_items(case)
    got = decode_tile_batch(items, use_pallas=use_pallas,
                            interpret=use_pallas)
    want = _ref_decode_tile_batch(items, use_pallas, use_pallas)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def test_empty_batch_dispatches_nothing():
    assert decode_tile_batch([]) == []


@pytest.mark.parametrize("case", ["tails", "multi_gop", "roi_masks"])
def test_gather_into_a_used_buffer_builds_the_host_stream(case):
    """The stream gathered into a reused (dirty) buffer is the one the
    host relayout built into zeros: every other byte is zeroed."""
    import repro.codec.batch as batch

    by_depth = {}
    for enc, idx, fw, blocks in _layout_items(case):
        idx = list(range(len(enc["kq"]))) if idx is None else idx
        n = enc["gop"] if fw is None else fw
        bsel = None if blocks is None else np.asarray(blocks, np.intp)
        by_depth.setdefault((enc["qp"], pad_bucket(n, lo=1)), []).append(
            (enc, idx, n, bsel))
    for (_, f_bucket), group in by_depth.items():
        slots, ref, off = [], [], 0
        for enc, idx, n, bsel in group:
            nb = (enc["h"] // 8) * (enc["w"] // 8) if bsel is None \
                else bsel.size
            slots.append(batch._Slot((0, enc, idx), n, len(idx), bsel, nb,
                                     off, len(idx) * nb))
            ref.append((enc, idx, n, bsel, off, len(idx) * nb))
            off += len(idx) * nb
        m_pad = pad_bucket(off, lo=MIN_COLUMNS)
        q = np.full((f_bucket, m_pad, 64), -7, dtype=np.int16)
        batch._gather(slots, q)
        np.testing.assert_array_equal(q.reshape(f_bucket, m_pad, 8, 8),
                                      _ref_gather(ref, f_bucket, m_pad))


def _warm_items(depth, m, encs):
    """What the benchmark's ``warm_shapes`` decodes for one (depth, column
    bucket): the first ``m`` blocks of the tiles, as block masks."""
    items = []
    for enc in encs:
        nb = (enc["h"] // 8) * (enc["w"] // 8)
        take = min(nb, m)
        if take:
            items.append((enc, [0], depth, tuple(range(take))))
        m -= take
    return items


def test_mixed_full_and_roi_slots_reuse_the_warmed_programs():
    from repro.kernels.decode import ops

    rng = np.random.default_rng(11)
    encs = [_rand_enc(rng, bh * 8, bw * 8, 8, 8, 2)
            for bh, bw in [(4, 6), (6, 5), (5, 8), (3, 3)]]
    total = sum((e["h"] // 8) * (e["w"] // 8) for e in encs)
    depths, buckets = [1, 2, 4, 8], [64, total]
    for depth in depths:
        for m in buckets:
            decode_tile_batch(_warm_items(depth, m, encs))
    warmed = ops._decode_fused._cache_size()
    # the same buckets, now with whole tiles (no mask) beside ROI masks
    mixed = [(encs[0], [0], 8, None), (encs[1], [1], 8, (0, 7, 9)),
             (encs[2], [0], 3, None), (encs[3], [1], 3, (1, 2)),
             (encs[2], [1], 1, (4,)), (encs[1], [0], 2, None)]
    for depth in depths:
        mixed.append((encs[3], [0], depth, None))
    everything = [(e, [0], 8, None) for e in encs]
    got = [decode_tile_batch(items) for items in (mixed, everything)]
    assert ops._decode_fused._cache_size() == warmed
    for items, canvases in zip((mixed, everything), got):
        want = _ref_decode_tile_batch(items, False, False)
        for g, w in zip(canvases, want):
            np.testing.assert_array_equal(g, w)


def test_counters_once_per_dispatch_with_the_bytes_moved(monkeypatch):
    import repro.codec.batch as batch
    from repro.utils import trace

    rec = trace.Recorder()
    monkeypatch.setattr(trace, "count", rec.count)
    moved = []
    real = batch.decode_canvas_op

    def spy(q, tab, **kw):
        out = real(q, tab, **kw)
        moved.append((q.nbytes + tab.nbytes, out.nbytes))
        return out

    monkeypatch.setattr(batch, "decode_canvas_op", spy)
    items = _layout_items("two_qp_groups") + _layout_items("multi_gop")
    decode_tile_batch(items)
    got = {name: [r.value for r in rec.window(0.0, np.inf)
                  if r.name == name]
           for name in ("tasm.decode.h2d_bytes", "tasm.decode.d2h_bytes",
                        "tasm.decode.view_slots", "tasm.decode.host_slots")}
    assert len(moved) == 3          # (qp, depth bucket): (8, 8), (12, 8), (8, 2)
    assert got["tasm.decode.h2d_bytes"] == [h for h, _ in moved]
    assert got["tasm.decode.d2h_bytes"] == [d for _, d in moved]
    slots = [v + hst for v, hst in zip(got["tasm.decode.view_slots"],
                                       got["tasm.decode.host_slots"])]
    assert len(slots) == 3 and sum(slots) == len(items)
    # views: whole tiles of one GOP; joined GOPs and ROI masks are host
    want_views = sum(1 for enc, idx, fw, blocks in items
                     if blocks is None and idx is not None and len(idx) == 1)
    assert sum(got["tasm.decode.view_slots"]) == want_views
