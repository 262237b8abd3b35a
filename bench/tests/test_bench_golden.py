"""The work every cell asks is fixed: each mix draws the same requests, and
the corpus the same archives, for a seed, down to the byte.

A change to ``traffic.py``, ``corpus.py`` or a mix that alters them changes
what an existing cell measures; these digests catch it.  Where such a
change is meant, the cell's numbers start over, and the digests are taken
anew.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import corpus  # noqa: E402
import run  # noqa: E402

#: sha256 of each mix's load-generator job and requests by key:
#: (cell, seed, full size) -> digest
REQUESTS = {
    ("vr2k-select", 7, True): "0234c0470767756ced9472a59135f42bd7cb8ccd485620cc5c6d75912d20ae25",
    ("vr2k-select", 7, False): "69e60fea483aa35ea52e493eee9d6d22ae823a717e59ef87d7c866091f11472d",
    ("vr2k-select", 2147483659, True): "d30048dd595bf2cf0486f0e8c3c012d695ce900d1d15ad683eeec296a431e5ab",
    ("vr2k-select", 2147483659, False): "a1d3a8ab05b7f10577b5631ed2b4301459a6661fb9a3ac7841a25c8f023b6504",
    ("vr2k-scan", 7, True): "fbbdfee46c7fb33f5c21338a1d28d9be9b983f6f624e57fdf0624c7db8f42e94",
    ("vr2k-scan", 7, False): "adf17279f46278a92d5c9398a680e0c19a9931716676e2756568ff3ea5a1d600",
    ("vr2k-scan", 2147483659, True): "208a2e5f7098a6dcaa67f56dfa9001aa96346a5f0a443c8fcf0dbd5230febf68",
    ("vr2k-scan", 2147483659, False): "d2f1476e3e70412c5efce0edf4a0a4058d0b968501c71ebdd9459ea45f3999d0",
    ("mot16-select-zipf", 7, True): "a4388e0843981807e258fb9c7bb1ace6a432851a6ff879481eaccb9cbc77a1f7",
    ("mot16-select-zipf", 7, False): "d403bb0fbd55a9ea1f0bc203f2b4c5d231a9672f5f8d43d96b5ab994d7018257",
    ("mot16-select-zipf", 2147483659, True): "1ca10ebb624fe162f808499f5b6477abf346dcc4bedef64d784489d9bbe063d8",
    ("mot16-select-zipf", 2147483659, False): "9053baf44cc94ab65837e69c14420d7289e53d95754c3a5d78ae257387e35ac4",
    ("vr2k-k2x4-select", 7, True): "469c7d34e88e7112c159802fd892e01c3cb5cd135e729fcb5f18dba92873ac0d",
    ("vr2k-k2x4-select", 7, False): "ba86a696160b274b55511ff8ea29db75a71f0724233e58cffb1ce35403bf681c",
    ("vr2k-k2x4-select", 2147483659, True): "dc51c472fa11633ee3bd2bd568ca0c50b1ebd4bd7b52738b1b69df49b6ab9606",
    ("vr2k-k2x4-select", 2147483659, False): "65bbf359f74d2a99cb8e21ecb5944e87313f7335339120817072d2b602b71dc4",
}


@pytest.mark.parametrize("key", sorted(REQUESTS, key=str))
def test_existing_mixes_draw_the_same_requests(key):
    cell, seed, full = key
    spec = run.load_cell(cell)
    cfg = spec["config"] if full else run.rehearsal_size(spec["config"])
    job, by_key = run.job_for(spec, cfg, seed, 45.0)
    doc = json.dumps([job, sorted(by_key.items())])
    assert hashlib.sha256(doc.encode()).hexdigest() == REQUESTS[key]


#: sha256 of the rehearsal-size archive's frames and detections:
#: (config, seed) -> digest
ARCHIVES = {
    ("visualroad-2k-kqko", 7): "51ac00c8d9b724f668c6f199b65b4fbf9161f8b43ed8d886f18bb2328864a5a5",
    ("visualroad-2k-kqko", 2147483659): "50eb95d5352edf4f2b9462d7d84f10f89e668017491c0e2c78eace9290a8f059",
    ("mot16-1080p-served", 7): "c36a4d17d84a7964a38c9c34e8a084c003f47266895ed575175eebf79273a3b6",
    ("mot16-1080p-served", 2147483659): "e1f823cf684bad305a8a8f649bb4f04287e807c44dc28f24b0af735e094860d1",
}


@pytest.mark.parametrize("key", sorted(ARCHIVES, key=str))
def test_one_camera_archives_are_unchanged(key):
    name, seed = key
    cfg = run.rehearsal_size(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))
    frames, dets = corpus.generate(cfg, seed)
    h = hashlib.sha256(frames.tobytes())
    h.update(json.dumps(dets).encode())
    assert h.hexdigest() == ARCHIVES[key]


#: sha256 of each camera's rehearsal-size archive in a cluster cell, made
#: from ``[seed, camera]``: (config, seed, camera) -> digest
CAMERA_ARCHIVES = {
    ("visualroad-2k-k2x4", 7, 0): "51ac00c8d9b724f668c6f199b65b4fbf9161f8b43ed8d886f18bb2328864a5a5",
    ("visualroad-2k-k2x4", 7, 1): "411daf1dab392f6a932d10584ac60ca67406e5d2f3e1ea47398dcb3256c4496f",
    ("visualroad-2k-k2x4", 7, 2): "37fa703dd6567c0995c43b798bf9925c1089d48538cfdfb7fc464cf6eae78ef3",
    ("visualroad-2k-k2x4", 7, 3): "f266a56611f39cefb8f35618eb0e17589267be51a8b5b5eedc61940e0c632224",
    ("visualroad-2k-k2x4", 2147483659, 0): "50eb95d5352edf4f2b9462d7d84f10f89e668017491c0e2c78eace9290a8f059",
    ("visualroad-2k-k2x4", 2147483659, 1): "00cc45f94a383861775be61817929f9c0004ee7194ae31f9f1c2c7e7a7601ba0",
    ("visualroad-2k-k2x4", 2147483659, 2): "86feb261c281cc8552f53b071f9cb4feb5d378868c383151b9731608b29c0d4a",
    ("visualroad-2k-k2x4", 2147483659, 3): "fa1bb6975e559c50c3d09cd3c221b12280c5aec6a86aa2efe052adc3cc04c5b0",
}


@pytest.mark.parametrize("key", sorted(CAMERA_ARCHIVES, key=str))
def test_camera_archives_are_unchanged(key):
    name, seed, camera = key
    cfg = run.rehearsal_size(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))
    frames, dets = corpus.generate(cfg, [seed, camera])
    h = hashlib.sha256(frames.tobytes())
    h.update(json.dumps(dets).encode())
    assert h.hexdigest() == CAMERA_ARCHIVES[key]
