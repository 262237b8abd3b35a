#!/usr/bin/env python3
"""Bring-up smoke of the served subframe-selection path on a TPU chip.

    python chip_smoke.py              # tasm_serve.py on one TPU chip
    python chip_smoke.py --chips 4    # 4 nodes, one chip each, behind
                                      # tasm_router.py --replication 2
    python chip_smoke.py --rehearse   # the same phases at 96x160, server
                                      # on the CPU (add --chips 4 for the
                                      # cluster phase)

Drives the entry points a deployment uses: ``scripts/tasm_serve.py
--decode-backend batched --transport shm`` (``RemoteVideoStore`` -> the
shared ``ServingSession`` -> ``TileStore.decode_tiles`` -> ``codec/batch.py``
-> the Pallas decode kernel), over a 1920x1080 camera of 90 frames (3 GOPs
of 30 at qp 8) generated from ``--seed``.  The per-frame shape is the
paper's traffic-camera setting at Visual Road's 2K resolution; the frame
count is the only cut.

Phases, each of which must pass:

1. the server (each node) reports its decode device: ``tpu`` with one chip
   per process (4 distinct chips with ``--chips 4``);
2. ingest + detections over the wire;
3. a cold pass by one client process: ``car``/``person`` subframe
   selections over overlapping frame ranges (ROI block masks at GOP
   depths 4, 8, 16 and 32) and one full-frame scan of one GOP (a
   ``[32, 32768, 8, 8]`` block stream);
4. a warm repeat by a fresh client process, which must decode 0 tiles;
5. every region of both passes against the numpy float32 oracle
   (``codec/encode.decode_tile``, via an in-process numpy-backend store
   built identically): region keys equal, pixels within ``ORACLE_ATOL``;
6. no background decode (tuner retile, prefetch) failed, and SIGTERM
   shuts every server process down with exit 0.

Only server processes touch the chip: this parent, its clients and the
router run with ``JAX_PLATFORMS=cpu`` and never start a JAX backend.  A
server gets ``JAX_PLATFORMS=tpu`` (``cpu`` only under ``--rehearse``), so a
host without a chip fails loudly.  The last line of stdout is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
The seconds printed are bring-up facts, compile included where labelled.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
SCRIPTS = ROOT / "scripts"

VIDEO = "cam0"
GOP, QP, N_FRAMES = 30, 8, 90
#: label of the whole-frame detections on GOP 1 (a scene-level tag)
FULL_FRAME = "frame"
FULL_FRAME_GOP = (GOP, 2 * GOP)
#: (label, frame range), in this order: shallow decodes first, so every
#: F bucket really dispatches before a deeper decode could serve it
WORKLOAD = [("car", (30, 38)),        # one SOT at depth 8
            ("person", (60, 64)),     # one SOT at depth 4
            ("person", (10, 45)),     # depths 30 and 15 across two SOTs
            ("car", (0, 90)),         # every SOT at depth 30
            (FULL_FRAME, FULL_FRAME_GOP)]   # a whole 1080p GOP
#: wire frames carry the raw 1080p corpus (746 MB of float32) on ingest
MAX_FRAME_MB = 1024
#: tile-cache budget of a server: the whole workload stays resident, so
#: the warm repeat is served without decoding
CACHE_BYTES = 1 << 30


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def fact(msg: str) -> None:
    print(f"# {msg}", flush=True)


# --------------------------------------------------------------- client
def client_main(addr: str, out: str, cluster: bool) -> int:
    """Run the workload once over the wire; write regions + meta to
    ``OUT.npz`` / ``OUT.json`` for the parent to check."""
    import numpy as np

    from repro.core import ClusterClient, RemoteVideoStore

    cls = ClusterClient if cluster else RemoteVideoStore
    arrays, meta = {}, []
    with cls(addr, max_frame_bytes=MAX_FRAME_MB << 20) as cli:
        for i, (label, rng) in enumerate(WORKLOAD):
            t0 = time.perf_counter()
            r = cli.scan(VIDEO).labels(label).frames(*rng).execute()
            seconds = time.perf_counter() - t0
            regs = []
            for j, (f, box, px) in enumerate(r.regions):
                arrays[f"px_{i}_{j}"] = np.array(px)
                regs.append([int(f), [int(v) for v in box]])
            meta.append({"seconds": seconds, "regions": regs,
                         "cache_misses": r.stats.cache_misses,
                         "transport": cli.transport})
        np.savez(out + ".npz", **arrays)
    pathlib.Path(out + ".json").write_text(json.dumps(meta))
    return 0


def run_client(addr: str, out: str, cluster: bool) -> list:
    """One fresh client process through the workload; its results."""
    import numpy as np

    cmd = [sys.executable, str(ROOT / "chip_smoke.py"), "--client", addr,
           "--out", out] + (["--cluster"] if cluster else [])
    rc = subprocess.run(cmd, timeout=900).returncode
    check(rc == 0, f"client process exited {rc}")
    meta = json.loads(pathlib.Path(out + ".json").read_text())
    with np.load(out + ".npz") as npz:
        for i, m in enumerate(meta):
            m["regions"] = [(f, tuple(box), npz[f"px_{i}_{j}"])
                            for j, (f, box) in enumerate(m["regions"])]
    return meta


# --------------------------------------------------------------- parent
def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_for_socket(path: str, proc, timeout: float = 180.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        check(proc.poll() is None,
              f"{path}: server died early (rc={proc.returncode})")
        if os.path.exists(path):
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            try:
                s.connect(path)
                return
            except OSError:
                pass
            finally:
                s.close()
        time.sleep(0.05)
    raise SmokeFailure(f"socket {path} never came up")


def start(script: str, args: list, env: dict) -> subprocess.Popen:
    # children log to our stderr: stdout carries only this script's facts
    return subprocess.Popen([sys.executable, str(SCRIPTS / script), *args],
                            env=env, stdout=sys.stderr)


def corpus(seed: int, height: int, width: int):
    """Frames + per-frame detections; GOP 1 also carries a whole-frame
    ``FULL_FRAME`` detection, so one scan decodes a full GOP."""
    from repro.data.video_gen import generate, sparse_spec

    frames, dets = generate(sparse_spec(seed=seed, n_frames=N_FRAMES,
                                        height=height, width=width))
    lo, hi = FULL_FRAME_GOP
    by_frame = {f: list(d) + ([(FULL_FRAME, (0, 0, height, width))]
                              if lo <= f < hi else [])
                for f, d in enumerate(dets)}
    return frames, by_frame


def oracle(frames, dets, encoder) -> list:
    """The workload on an in-process numpy-backend store: every pixel comes
    from ``codec/encode.decode_tile``, the float32 oracle."""
    from repro.core import (CacheConfig, DecodeConfig, NoTilingPolicy,
                            VideoStore)

    with VideoStore(decode=DecodeConfig(backend="numpy"),
                    cache=CacheConfig(budget_bytes=0)) as local:
        local.add_video(VIDEO, encoder=encoder, policy=NoTilingPolicy())
        local.ingest(VIDEO, frames)
        local.add_detections(VIDEO, dets)
        return [local.scan(VIDEO).labels(label).frames(*rng).execute().regions
                for label, rng in WORKLOAD]


def compare(passes: dict, reference: list) -> float:
    """Region keys equal, pixels within ORACLE_ATOL; returns max abs err."""
    import numpy as np

    from repro.codec.batch import ORACLE_ATOL

    worst = 0.0
    for name, meta in passes.items():
        for (label, rng), m, ref in zip(WORKLOAD, meta, reference):
            got = m["regions"]
            where = f"{name} {label} {rng}"
            check(len(got) == len(ref),
                  f"{where}: {len(got)} regions vs oracle {len(ref)}")
            for g, r in zip(got, ref):
                check(g[:2] == (r[0], tuple(r[1])),
                      f"{where}: region key {g[:2]} vs oracle {r[:2]}")
                check(g[2].shape == r[2].shape and g[2].dtype == r[2].dtype,
                      f"{where}: frame {g[0]} is {g[2].dtype}{g[2].shape}, "
                      f"oracle {r[2].dtype}{r[2].shape}")
                err = float(np.max(np.abs(g[2] - r[2]))) if g[2].size else 0.
                check(err <= ORACLE_ATOL,
                      f"{where}: frame {g[0]} max abs err {err} > "
                      f"{ORACLE_ATOL}")
                worst = max(worst, err)
    return worst


def check_devices(devices: dict, platform: str) -> dict:
    """Every server reports ``platform`` with one device, each on its own
    chip; returns the last line's device block."""
    for name, dev in devices.items():
        check(dev is not None, f"{name}: no device block (numpy backend?)")
        fact(f"{name} decodes on {dev['platform']} {dev['device_kind']!r}, "
             f"count {dev['count']}, ids {dev['ids']}, visible chips "
             f"{dev['visible_chips']}")
        check(dev["platform"] == platform,
              f"{name}: decode platform {dev['platform']!r}, want "
              f"{platform!r}")
        check(dev["count"] == 1, f"{name}: sees {dev['count']} devices")
    if len(devices) > 1:
        chips = [d["visible_chips"] for d in devices.values()]
        check(None not in chips and len(set(chips)) == len(chips),
              f"nodes not confined to distinct chips: {chips}")
    first = next(iter(devices.values()))
    return {"platform": first["platform"], "kind": first["device_kind"],
            "count": sum(d["count"] for d in devices.values())}


def smoke(args) -> dict:
    from repro.codec.encode import EncoderConfig
    from repro.core import ClusterClient, NoTilingPolicy, RemoteVideoStore

    platform = "cpu" if args.rehearse else "tpu"
    height, width = (96, 160) if args.rehearse else (1080, 1920)
    encoder = EncoderConfig(gop=GOP, qp=QP)
    cluster = args.chips == 4
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    server_env = dict(env, JAX_PLATFORMS=platform)
    serve_args = ["--decode-backend", "batched", "--transport", "shm",
                  "--cache-bytes", str(CACHE_BYTES),
                  "--max-frame-mb", str(MAX_FRAME_MB)]
    procs: dict[str, subprocess.Popen] = {}
    try:
        if cluster:
            socks = {f"n{i}": os.path.join(tmp, f"n{i}.sock")
                     for i in range(4)}
            for i, (name, sock) in enumerate(socks.items()):
                port = free_port()
                confine = [f"TPU_VISIBLE_CHIPS={i}",
                           "TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1",
                           "TPU_PROCESS_BOUNDS=1,1,1",
                           f"TPU_PROCESS_PORT={port}",
                           f"TPU_PROCESS_ADDRESSES=localhost:{port}"]
                procs[name] = start(
                    "tasm_serve.py",
                    ["--socket", sock, *serve_args,
                     *[a for kv in confine for a in ("--env", kv)]],
                    server_env)
            for name, sock in socks.items():
                wait_for_socket(sock, procs[name])
            addr = os.path.join(tmp, "router.sock")
            procs["router"] = start(
                "tasm_router.py",
                ["--socket", addr, "--replication", "2",
                 "--max-frame-mb", str(MAX_FRAME_MB),
                 *[a for n, s in socks.items()
                   for a in ("--node", f"{n}={s}")]],
                env)
            wait_for_socket(addr, procs["router"])
            connect = ClusterClient
        else:
            addr = os.path.join(tmp, "tasm.sock")
            procs["server"] = start("tasm_serve.py",
                                    ["--socket", addr, *serve_args],
                                    server_env)
            wait_for_socket(addr, procs["server"])
            connect = RemoteVideoStore

        def stats():
            with connect(addr) as c:
                return c.stats()

        doc = stats()
        device = check_devices(
            {n: (d or {}).get("device") for n, d in doc["nodes"].items()}
            if cluster else {"server": doc["device"]}, platform)

        t0 = time.perf_counter()
        frames, dets = corpus(args.seed, height, width)
        fact(f"corpus: {N_FRAMES} frames {width}x{height}, GOP {GOP}, qp "
             f"{QP}, seed {args.seed}; generated in "
             f"{time.perf_counter() - t0:.3f} s")
        with connect(addr, max_frame_bytes=MAX_FRAME_MB << 20) as c:
            c.add_video(VIDEO, encoder=encoder, policy=NoTilingPolicy())
            t0 = time.perf_counter()
            c.ingest(VIDEO, frames)
            ingest_s = time.perf_counter() - t0
            c.add_detections(VIDEO, dets)
        fact(f"ingest_s {ingest_s:.3f} (over the wire, server-side encode "
             f"included{', 2 replicas' if cluster else ''})")
        t0 = time.perf_counter()
        reference = oracle(frames, dets, encoder)
        fact(f"oracle: numpy decode_tile in-process, "
             f"{time.perf_counter() - t0:.3f} s (host CPU)")
        del frames

        cold = run_client(addr, os.path.join(tmp, "cold"), cluster)
        before = stats()["tiles_decoded_total"]
        check(before > 0, "cold pass decoded no tiles")
        secs = [round(m["seconds"], 3) for m in cold]
        fact(f"first_scan_s {secs[0]} (compile included)")
        fact(f"cold pass per-scan s {secs} (first use of each decode "
             f"bucket compiles) over {[q for q, _ in WORKLOAD]}")
        fact(f"cold pass regions per scan "
             f"{[len(m['regions']) for m in cold]}, transport "
             f"{cold[0]['transport']}")

        warm = run_client(addr, os.path.join(tmp, "warm"), cluster)
        doc = stats()
        misses = sum(m["cache_misses"] for m in warm)
        check(misses == 0, f"warm repeat had {misses} cache misses")
        check(doc["tiles_decoded_total"] == before,
              f"warm repeat decoded {doc['tiles_decoded_total'] - before} "
              f"tiles")
        fact(f"warm repeat from a fresh client decoded 0 tiles; per-scan s "
             f"{[round(m['seconds'], 3) for m in warm]}")
        fact(f"decoded on the server(s): tiles_decoded_total "
             f"{doc['tiles_decoded_total']}, pixels_decoded_total "
             f"{doc['pixels_decoded_total']}")

        from repro.codec.batch import ORACLE_ATOL
        worst = compare({"cold": cold, "warm": warm}, reference)
        n = sum(len(r) for r in reference)
        fact(f"max abs error vs numpy oracle {worst!r} (tolerance "
             f"{ORACLE_ATOL}) over {n} regions x 2 passes; region keys equal")

        # background decodes (tuner retiles, prefetch) re-raise here
        with connect(addr) as c:
            c.drain_tuner(timeout=300)
            c.drain_prefetch(timeout=300)

        # router first, so it never sees a node vanish under it
        for name in sorted(procs, key=lambda n: n != "router"):
            procs[name].send_signal(signal.SIGTERM)
            rc = procs[name].wait(timeout=120)
            check(rc == 0, f"{name} exited {rc} on SIGTERM")
        fact(f"SIGTERM: {', '.join(procs)} exited 0")
        return device
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
        shutil.rmtree(tmp, ignore_errors=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: one tasm_serve.py on one chip; 4: only the "
                         "cluster phase, 4 one-chip nodes behind a K=2 "
                         "router")
    ap.add_argument("--rehearse", action="store_true",
                    help="same phases at 96x160 with the servers on the CPU")
    ap.add_argument("--seed", type=int, default=0,
                    help="corpus seed (default 0)")
    ap.add_argument("--client", metavar="ADDR", help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    ap.add_argument("--cluster", action="store_true", help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    if args.client:
        return client_main(args.client, args.out, args.cluster)
    try:
        device = smoke(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # this process and its clients never start an accelerator backend:
    # only the server processes it launches may hold a chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    raise SystemExit(main())
