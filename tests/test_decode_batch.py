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
from repro.kernels.decode import MIN_COLUMNS, decode_fused_ref, pad_bucket

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
