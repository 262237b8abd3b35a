#!/usr/bin/env python3
"""One node of a cluster cell: a process that holds one chip.

    python bench/node.py --workload vr2k-k2x4-select --socket PATH \
        [--rehearse] [--fault KIND]

Started by ``cluster.py``, which confines it to its chip through the
environment.  It builds the configuration's ``VideoStore`` and a
``VideoStoreServer`` on the Unix socket ``PATH``, which the router dials,
and then answers commands on a pipe: one JSON line in on stdin, one JSON
line out on its standard output (anything else that prints goes to
stderr).  Its first line is ``{"device": {...}}`` once the server is up, or
``{"no_device": why}`` where JAX finds no accelerator (the device check).

    {"op": "warm"}                 -> {"shapes": n}: a decode at every stream
                                      shape of the mix, once
    {"op": "trace", "dir": d}      -> {}: start the profiler
    {"op": "window", "t0": t, "seconds": s}
                                   -> {"pixels", "compiles", "spans"}: the
                                      window's decoded pixels, compiles and
                                      span records ([name, value]; null where
                                      records of it were dropped); stops the
                                      profiler
    {"op": "memory"}               -> {"peak_bytes": n}
    {"op": "stop"}                 -> {}: stops the server and exits

``--fault`` plants a fault for the tests: ``start`` raises while the store
is built; ``corrupt_sot`` adds 4 to the intra DC coefficient of every
stored block of one SOT, the last of the first video the node holds (after
ingest, before the warm-up);
``altered`` adds 0.01 to every decoded pixel; ``high`` decodes at
``Precision.HIGH`` with the reference's arithmetic (``control.py``), one
step below the precision the configuration states.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


class Node:
    def __init__(self, args):
        import jax
        from jax import monitoring

        self.jax = jax
        spec = run.load_cell(args.workload)
        self.mix = spec["mix"]
        self.cfg = run.rehearsal_size(spec["config"]) if args.rehearse \
            else spec["config"]
        self.fault = args.fault
        self.compiles, self.counting, self.tracing = 0, False, False

        def on_event(event, *_a, **_k):
            if self.counting and event.endswith("backend_compile_duration"):
                self.compiles += 1

        monitoring.register_event_duration_secs_listener(on_event)
        if self.fault == "start":
            raise RuntimeError("planted fault: the node fails to start")
        if self.fault in ("altered", "high"):
            _plant_decode(self.fault)
        from repro.core import (CacheConfig, DecodeConfig, TuningConfig,
                                VideoStore, VideoStoreServer)
        sv = self.cfg["serving"]
        self.store = VideoStore(
            cache=CacheConfig(budget_bytes=sv["cache_bytes"]),
            tuning=TuningConfig(mode=sv["tuning"]),
            decode=DecodeConfig(backend=sv["decode_backend"]))
        self.server = VideoStoreServer(
            self.store, path=args.socket, transport=sv["transport"],
            max_batch=sv["max_batch"],
            max_frame_bytes=sv["max_frame_mb"] << 20).start()

    def op_warm(self) -> dict:
        videos = self.store.videos()
        if self.fault == "corrupt_sot" and videos:
            ts = self.store.video(videos[0]).store
            _corrupt(ts, ts.sots[-1])
        # the programs are keyed by qp and stream shape: one video warms
        # them for every video the node holds
        n = run.warm_shapes(self.store.video(videos[0]).store, self.cfg,
                            self.mix) if videos else 0
        return {"shapes": n}

    def op_trace(self, dir: str) -> dict:  # noqa: A002 - the command's key
        self.jax.profiler.start_trace(dir, profiler_options=run._quiet())
        self.tracing = True
        return {}

    def op_window(self, t0: float, seconds: float) -> dict:
        from repro.utils import trace

        pixels = self.store.stats()["pixels_decoded_total"]
        time.sleep(max(0.0, t0 - time.monotonic()))
        self.counting = True
        with self.jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        self.counting = False
        pixels = self.store.stats()["pixels_decoded_total"] - pixels
        if self.tracing:
            self.jax.profiler.stop_trace()
            self.tracing = False
        recs = trace.window(t0, t0 + seconds)
        return {"pixels": pixels, "compiles": self.compiles,
                "spans": None if recs is None
                else [[r.name, r.value] for r in recs]}

    def op_memory(self) -> dict:
        st = self.jax.devices()[0].memory_stats() or {}
        return {"peak_bytes": int(st.get("peak_bytes_in_use", 0))}

    def op_stop(self) -> dict:
        self.server.stop()
        return {}


def _plant_decode(kind: str) -> None:
    import numpy as np

    import repro.codec.batch as batch

    if kind == "high":
        import control

        batch.decode_fused_op = control.decode_op("high")
        return
    real = batch.decode_tile_batch

    def altered(items, **kw):
        return [a + np.float32(0.01) for a in real(items, **kw)]

    batch.decode_tile_batch = altered


def _corrupt(ts, rec) -> None:
    """Add 4 to the intra DC coefficient of every block of SOT ``rec`` of
    the tile store ``ts`` (its in-memory tile streams, in place)."""
    for t in range(rec.layout.n_tiles):
        ts._read_tile(rec, t)["kq"][..., 0, 0] += 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--socket", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--fault", choices=run.NODE_FAULTS)
    args = ap.parse_args(argv)
    # the command pipe keeps the real stdout; stray prints go to stderr
    out = os.fdopen(os.dup(1), "w", buffering=1)
    os.dup2(2, 1)

    def reply(doc: dict) -> None:
        out.write(json.dumps(doc) + "\n")

    run.prepare_env(args.rehearse)
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:       # no backend for JAX_PLATFORMS
        reply({"no_device": str(e)})
        return 3
    node = Node(args)
    d = devs[0]
    reply({"device": {"platform": d.platform, "kind": d.device_kind,
                      "count": len(devs), "id": d.id,
                      "coords": list(getattr(d, "coords", None) or [])}})
    for line in sys.stdin:
        cmd = json.loads(line)
        op = cmd.pop("op")
        reply(getattr(node, f"op_{op}")(**cmd))
        if op == "stop":
            break
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
