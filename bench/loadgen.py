"""Load-generator process: drives one cell's requests over the wire.

Started by ``run.py`` with ``JAX_PLATFORMS=cpu``; it talks to the server
only through ``RemoteVideoStore`` and never starts a JAX backend.  It reads
one job (JSON line) from stdin, connects, answers ``ready``, reads the
window's start time (a second JSON line), runs the job, and writes to
stdout one JSON line --
a record per request -- followed by the raw float32 pixels of the sampled
answers, whose shapes the JSON line gives.

A request with a ``camera`` asks that camera's video (``job["videos"]``);
any other asks the cell's one video (``job["video"]``).
Open loop: one thread sends each request when it is due and never waits
for replies; a reply's completion time is taken when the client has it.
Closed loop: one thread per client, each with its own connection.
Latency runs from when a request was due (open loop) or sent (closed loop)
to its completion.  Requests still out when the window closes are waited
for up to ``grace`` seconds; those that never come are reported lost.
"""
from __future__ import annotations

import json
import sys
import threading
import time


def _summary(res) -> dict:
    s = res.stats
    return {"lookup_s": s.lookup_s, "decode_s": s.decode_s,
            "marshal_s": s.marshal_s, "payload_bytes": s.payload_bytes,
            "cache_hits": s.cache_hits, "cache_misses": s.cache_misses,
            "pixels_decoded": s.pixels_decoded, "regions": s.regions,
            "transport": s.transport,
            "region_px": int(sum(px.size for _, _, px in res.regions))}


class Collector:
    def __init__(self):
        self.lock = threading.Lock()
        self.samples: list[tuple[dict, list]] = []   # (meta, arrays)

    def keep(self, rec: dict, res) -> None:
        import numpy as np
        regions = [(int(f), [int(v) for v in box], np.array(px, np.float32))
                   for f, box, px in res.regions]
        meta = {"key": rec["key"],
                "regions": [[f, box, list(px.shape)]
                            for f, box, px in regions]}
        with self.lock:
            self.samples.append((meta, [px for _, _, px in regions]))


def _finish(rec: dict, fut, sampled: bool, col: Collector) -> None:
    rec["done"] = time.monotonic()
    try:
        res = fut.result()
    except BaseException as e:  # noqa: BLE001 - recorded, counted failed
        rec["error"] = f"{type(e).__name__}: {e}"
        return
    rec["stats"] = _summary(res)
    if sampled:
        col.keep(rec, res)


def _video(job: dict, q: dict) -> str:
    """The video a request asks: its camera's, else the cell's one video."""
    return job["videos"][q["camera"]] if "camera" in q else job["video"]


def run_open(job: dict, store, col: Collector) -> list[dict]:
    t0 = job["t0"]
    sample = set(job["sample"])
    recs, futs = [], []
    for r in job["requests"]:
        rec = dict(r, key=r["i"])
        delay = t0 + r["due"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        rec["sent"] = time.monotonic()
        fut = store.scan(_video(job, r)).labels(r["label"]).frames(
            r["lo"], r["hi"]).submit()
        fut.add_done_callback(
            lambda f, rec=rec, s=r["i"] in sample: _finish(rec, f, s, col))
        recs.append(rec)
        futs.append(fut)
    deadline = t0 + job["seconds"] + job["grace"]
    for fut in futs:
        try:
            fut.result(timeout=max(0.0, deadline - time.monotonic()))
        except BaseException:  # noqa: BLE001 - lost or failed, see records
            pass
    return recs


def run_closed(job: dict, stores: list, col: Collector) -> list[dict]:
    t0 = job["t0"]
    end, deadline = t0 + job["seconds"], t0 + job["seconds"] + job["grace"]
    recs: list[dict] = []
    lock = threading.Lock()

    def client(c: int, store, queries: list, sample: set) -> None:
        delay = t0 - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        for seq, q in enumerate(queries):
            now = time.monotonic()
            if now >= end:
                return
            rec = dict(q, key=[c, seq], due=now - t0, sent=now)
            with lock:
                recs.append(rec)
            fut = store.scan(_video(job, q)).labels(q["label"]).frames(
                q["lo"], q["hi"]).submit()
            try:
                fut.result(timeout=max(0.0, deadline - now))
            except BaseException:  # noqa: BLE001 - see _finish
                pass
            if not fut.done():
                return      # never came: the record stays lost
            _finish(rec, fut, seq in sample, col)

    threads = [threading.Thread(target=client, args=(c, st, qs, set(sm)),
                                name=f"client{c}")
               for c, (st, qs, sm) in enumerate(zip(stores, job["clients"],
                                                    job["sample"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return recs


def main() -> int:
    job = json.loads(sys.stdin.readline())
    sys.path.insert(0, job["src"])
    from repro.core import RemoteVideoStore

    def connect():
        return RemoteVideoStore(job["addr"], transport="shm",
                                want_plans=False,
                                max_frame_bytes=job["max_frame_bytes"])

    col = Collector()
    n_conns = 1 if job["loop"] == "open" else len(job["clients"])
    stores = [connect() for _ in range(n_conns)]
    out = sys.stdout.buffer
    try:
        out.write(b"ready\n")
        out.flush()
        job["t0"] = json.loads(sys.stdin.readline())["t0"]
        if job["loop"] == "open":
            recs = run_open(job, stores[0], col)
        else:
            recs = run_closed(job, stores, col)
    finally:
        for s in stores:
            s.close()
    metas = [m for m, _ in col.samples]
    blob_bytes = sum(a.nbytes for _, arrs in col.samples for a in arrs)
    out.write((json.dumps({"records": recs, "samples": metas,
                           "blob_bytes": blob_bytes}) + "\n").encode())
    for _, arrs in col.samples:
        for a in arrs:
            out.write(memoryview(a).cast("B"))
    out.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
