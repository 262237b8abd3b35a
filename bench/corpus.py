"""The archive a cell serves: frames and detections made from ``--seed``.

A copy of the procedural Visual-Road-style generator of
``src/repro/data/video_gen.py``, kept here so that the benchmark's inputs do
not move when the program's generator does.  It departs from it so that
every seed asks the same amount of work of the system: the scene's geometry
-- each object's size (evenly spaced multipliers of the nominal size from
0.8 to 1.25), start and heading -- is drawn from a constant stream and is
the same on every seed, while the pixels (background noise, each object's
texture) are the seed's.  A selection's region boxes and the blocks they
touch are then the same on every seed; what is decoded differs.  The
configuration may also add a whole-frame scene tag on every frame, which a
detector's full scan selects.
"""
from __future__ import annotations

import numpy as np

#: (label, (y1, x1, y2, x2)) with half-open pixel bounds
Detection = tuple


def generate(cfg: dict, seed: int):
    """``(frames [T, H, W] float32 in [0, 255], detections)`` where
    ``detections[f]`` is the list of ``(label, bbox)`` of frame ``f``."""
    rng = np.random.default_rng(seed)
    scene = np.random.default_rng(0)
    t, h, w = cfg["n_frames"], cfg["height"], cfg["width"]

    # smoothed noise plus a low-frequency pattern: a background a codec can
    # code, with non-trivial residuals
    noise = rng.normal(0.0, 14.0, size=(h + 8, w + 16))
    k = np.ones(9) / 9.0
    noise = np.apply_along_axis(lambda r: np.convolve(r, k, mode="same"), 1,
                                noise)
    noise = np.apply_along_axis(lambda c: np.convolve(c, k, mode="same"), 0,
                                noise)
    bg = 110.0 + 3.0 * noise[4:h + 4, 4:w + 4]
    yy = np.linspace(0, 6 * np.pi, h)[:, None]
    xx = np.linspace(0, 6 * np.pi, w)[None, :]
    bg = np.clip(bg + 25 * np.sin(yy) * np.cos(xx), 0, 255).astype(np.float32)

    objs = []
    for spec in cfg["objects"]:
        n = spec["count"]
        scales = scene.permutation(np.linspace(0.8, 1.25, n)) if n > 1 \
            else np.ones(1)
        for s in scales:
            oh = max(8, min(h, int(spec["size"][0] * s)))
            ow = max(8, min(w, int(spec["size"][1] * s)))
            ang = scene.uniform(0, 2 * np.pi)
            tex = rng.normal(spec["intensity"], 4.0, size=(oh, ow))
            tex[::4] -= 12.0     # horizontal banding: structured texture
            objs.append({"label": spec["label"], "h": oh, "w": ow,
                         "y": scene.uniform(0, max(h - oh, 1)),
                         "x": scene.uniform(0, max(w - ow, 1)),
                         "vy": spec["speed"] * np.sin(ang),
                         "vx": spec["speed"] * np.cos(ang),
                         "tex": np.clip(tex, 0, 255).astype(np.float32)})

    tag = cfg.get("scene_tag")
    frames = np.empty((t, h, w), dtype=np.float32)
    detections: list[list[Detection]] = []
    for f in range(t):
        frame = bg.copy()
        dets: list[Detection] = []
        for o in objs:
            # integrate, bouncing off the frame's edges
            o["y"] += o["vy"]
            o["x"] += o["vx"]
            if o["y"] < 0 or o["y"] + o["h"] > h:
                o["vy"] = -o["vy"]
                o["y"] = float(np.clip(o["y"], 0, h - o["h"]))
            if o["x"] < 0 or o["x"] + o["w"] > w:
                o["vx"] = -o["vx"]
                o["x"] = float(np.clip(o["x"], 0, w - o["w"]))
            y, x = int(o["y"]), int(o["x"])
            frame[y:y + o["h"], x:x + o["w"]] = o["tex"]
            dets.append((o["label"], (y, x, y + o["h"], x + o["w"])))
        if tag:
            dets.append((tag, (0, 0, h, w)))
        frames[f] = frame
        detections.append(dets)
    return frames, detections

