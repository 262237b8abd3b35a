#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload vr2k-select --seed 7 --seconds 30 --trace 0
    python bench/run.py --workload vr2k-select --seed 7 --seconds 4 \
        --trace 0 --rehearse       # the same run on the CPU, at 96x160

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); its metrics are the entries of
``BENCHMARK.json`` that list it, each computed by
``bench/metrics/<metric>.py``.  Nothing here names a cell.

This process holds the chip.  It builds the configuration's ``VideoStore``
and a ``VideoStoreServer`` on a Unix socket (shared-memory replies) and
serves from the server's threads; it makes the archive from ``--seed``,
ingests it, decodes once at every stream shape the mix's requests can hand
the batched decode (so nothing compiles in the window), and, for a mix
that asks for it, asks its most popular catalogue queries once.  That is
the set-up.  A load generator
(``loadgen.py``, one process under ``JAX_PLATFORMS=cpu``) then offers the
mix for ``--seconds``; this process only waits, and with ``--trace 1``
records a profiler trace of the window.  After the window it reads the
device's peak memory, stops the server, and checks a sample of the answers
against ``reference.py``.

Stdout ends with one JSON line: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with its limit.  A host without the chips
the cell asks for exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from collections import Counter  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
SRC = ROOT / "src"
sys.path.insert(0, str(HERE))

import traffic  # noqa: E402

VIDEO = "cam0"
#: seconds a load generator waits past the window for outstanding replies
GRACE_S = 60.0
#: the batched decode's stream shapes: frame depths (powers of two up to a
#: GOP of 30) and column buckets (codec/batch.py pads to powers of two from
#: 64; one SOT of 1080p holds 32400 blocks)
DEPTH_BUCKETS = (1, 2, 4, 8, 16, 32)
#: the jitted decode program, as its device trace names it
DECODE_PROGRAM = "_decode_fused"
#: bytes a decoded pixel moves at least: an int16 coefficient in, a
#: float32 pixel out
BYTES_PER_PIXEL = 6


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


# ------------------------------------------------------------------ cells
def load_cell(name: str) -> dict:
    """Resolve a cell by name: its entry, configuration, mix and metrics."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {"cell": cell,
            "config": json.loads((ROOT / conf["file"]).read_text()),
            "mix": traffic.load(cell["traffic"]),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def reader(metric: str):
    """``bench/metrics/<metric>.py``'s ``read``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def rehearsal_size(cfg: dict) -> dict:
    """The configuration at 96x160 and 2 GOPs, objects scaled alike."""
    sy, sx = 96 / cfg["height"], 160 / cfg["width"]
    objs = [dict(o, size=[max(8, int(o["size"][0] * sy)),
                          max(8, int(o["size"][1] * sx))],
                 speed=o["speed"] * sx, count=min(o["count"], 6))
            for o in cfg["objects"]]
    return dict(cfg, height=96, width=160, n_frames=2 * cfg["gop"],
                objects=objs)


# ----------------------------------------------------------------- server
class Harness:
    """The system under test, set up for one cell in this process."""

    def __init__(self, spec: dict, seed: int, rehearse: bool):
        self.spec, self.seed, self.rehearse = spec, seed, rehearse
        self.cfg = rehearsal_size(spec["config"]) if rehearse \
            else spec["config"]
        self.tmp = tempfile.mkdtemp(prefix="tb")
        self.addr = os.path.join(self.tmp, "s")
        if len(self.addr) > 100:     # past what a Unix socket path holds
            shutil.rmtree(self.tmp)
            (HERE / ".cache").mkdir(exist_ok=True)
            self.tmp = tempfile.mkdtemp(prefix="tb", dir=HERE / ".cache")
            self.addr = os.path.relpath(os.path.join(self.tmp, "s"))
        self.server = None
        self.client = None
        self.compiles = 0
        self._counting = False

    # -- set-up -----------------------------------------------------------
    def start(self) -> dict:
        """Start JAX and the server; the device block."""
        import jax
        from jax import monitoring

        devs = jax.devices()
        want = self.spec["cell"]["chips"]
        if not self.rehearse and (devs[0].platform != "tpu"
                                  or len(devs) < want):
            raise NoChip(f"cell wants {want} TPU chip(s); JAX sees "
                         f"{len(devs)} {devs[0].platform} device(s)")

        def on_event(event, *_a, **_k):
            if self._counting and event.endswith("backend_compile_duration"):
                self.compiles += 1

        monitoring.register_event_duration_secs_listener(on_event)
        from repro.core import (CacheConfig, DecodeConfig, TuningConfig,
                                VideoStore, VideoStoreServer)
        sv = self.cfg["serving"]
        store = VideoStore(cache=CacheConfig(budget_bytes=sv["cache_bytes"]),
                           tuning=TuningConfig(mode=sv["tuning"]),
                           decode=DecodeConfig(backend=sv["decode_backend"]))
        self.server = VideoStoreServer(
            store, path=self.addr, transport=sv["transport"],
            max_batch=sv["max_batch"],
            max_frame_bytes=sv["max_frame_mb"] << 20).start()
        self.store = store
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}

    def spawn_client(self, job: dict) -> None:
        """Start the load generator; it connects while set-up goes on."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.client = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py")], env=env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        self.client.stdin.write((json.dumps(dict(
            job, addr=self.addr, video=VIDEO, src=str(SRC),
            max_frame_bytes=self.cfg["serving"]["max_frame_mb"] << 20))
            + "\n").encode())
        self.client.stdin.flush()

    def ingest(self) -> tuple:
        """Make the archive from the seed and ingest it; (frames, dets)."""
        import corpus
        from repro.codec.encode import EncoderConfig
        from repro.core import KQKOPolicy, NoTilingPolicy

        cfg = self.cfg
        frames, dets = corpus.generate(cfg, self.seed)
        enc = EncoderConfig(gop=cfg["gop"], qp=cfg["qp"])
        lay = cfg["layout"]
        dets_t = [[(lab, tuple(int(v) for v in box)) for lab, box in d]
                  for d in dets]
        if lay["policy"] == "kqko":
            policy = KQKOPolicy(lay["query_objects"])
            layouts = self._kqko_layouts(policy, dets_t, enc)
        else:
            policy, layouts = NoTilingPolicy(), None
        self.store.ingest(VIDEO, frames, detections=dets_t,
                          initial_layouts=layouts, encoder=enc,
                          policy=policy)
        return frames, dets_t

    def _kqko_layouts(self, policy, dets, enc) -> dict:
        """The layouts KQKO chooses at ingest, from the detections alone, so
        that each tile is encoded once from the source frames."""
        from repro.core import SemanticIndex

        index = SemanticIndex()
        for f, d in enumerate(dets):
            for lab, box in d:
                index.add(VIDEO, f, lab, box)
        gop, n = self.cfg["gop"], self.cfg["n_frames"]
        sots = [types.SimpleNamespace(sot_id=s, frame_start=s * gop,
                                      frame_end=(s + 1) * gop)
                for s in range(n // gop)]
        shim = types.SimpleNamespace(sots=sots, encoder=enc)
        return policy.on_ingest(index, shim, VIDEO,
                                (self.cfg["height"], self.cfg["width"]))

    def warm_shapes(self, mix: dict) -> int:
        """Decode once at every (depth bucket, column bucket) that the
        mix's requests can hand the batched decode: each call is one stream
        of exactly that shape.  Whole-GOP scans decode every block of a SOT
        at the GOP's depth, one shape; selections start anywhere, so they
        reach every depth up to their longest range and, merged, any
        column count up to a SOT's.  Returns the number of shapes."""
        ts = self.store.video(VIDEO).store
        rec = ts.sots[0]
        cells = [(t, b) for t in range(rec.layout.n_tiles)
                 for b in range(rec.layout.tile_blocks(t))]
        gop = self.cfg["gop"]
        if mix["queries"]["kind"] == "gop_cycle":
            depths, buckets = [gop], [len(cells)]
        else:
            top = min(traffic.longest(mix["queries"]), gop)
            depths = [min(d, gop) for d in DEPTH_BUCKETS
                      if d < 2 * top]
            buckets, m = [], 64
            while m // 2 < len(cells):
                buckets.append(min(m, len(cells)))
                m *= 2
        n = 0
        for depth in depths:
            for m in buckets:
                masks: dict = {}
                for t, b in cells[:m]:
                    masks.setdefault(t, []).append(b)
                ts.decode_tiles(rec.sot_id, sorted(masks), n_frames=depth,
                                blocks={t: tuple(v) for t, v in
                                        masks.items()})
                n += 1
        return n

    def warm_fill(self, queries: list) -> None:
        """Ask each query once, least popular first, so the most popular
        are the most recent (set-up the traffic needs: a dashboard's cache
        is warm)."""
        for q in reversed(queries):
            self.store.scan(VIDEO).labels(q["label"]).frames(
                q["lo"], q["hi"]).execute()

    def wait_ready(self) -> None:
        line = self.client.stdout.readline()
        if line.strip() != b"ready":
            raise RuntimeError(f"load generator did not start: {line!r}")

    # -- window -----------------------------------------------------------
    def window(self, seconds: float, trace_dir: str | None) -> dict:
        """Offer the mix for ``seconds``; counters around the window."""
        import jax

        pixels = self.store.stats()["pixels_decoded_total"]
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir, profiler_options=_quiet())
        t0 = time.monotonic() + 0.2
        self.client.stdin.write((json.dumps({"t0": t0}) + "\n").encode())
        self.client.stdin.flush()
        time.sleep(max(0.0, t0 - time.monotonic()))
        self._counting = True
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        self._counting = False
        pixels_in_window = self.store.stats()["pixels_decoded_total"] - pixels
        if trace_dir is not None:
            jax.profiler.stop_trace()
        return {"t0": t0, "pixels_in_window": pixels_in_window}

    def collect(self) -> tuple[list, list]:
        """The load generator's records and sampled answers."""
        import numpy as np

        out = self.client.stdout
        head = json.loads(out.readline())
        blob = out.read(head["blob_bytes"])
        rc = self.client.wait(timeout=60)
        if rc != 0 or len(blob) != head["blob_bytes"]:
            raise RuntimeError(f"load generator exited {rc}")
        samples, off = [], 0
        for meta in head["samples"]:
            regions = []
            for f, box, shape in meta["regions"]:
                n = int(np.prod(shape)) * 4
                px = np.frombuffer(blob, np.float32, n // 4, off)
                regions.append((f, tuple(box), px.reshape(shape)))
                off += n
            samples.append((meta["key"], regions))
        return head["records"], samples

    def memory_peak(self) -> int:
        import jax

        st = jax.devices()[0].memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0))

    def close(self) -> None:
        if self.client is not None and self.client.poll() is None:
            self.client.kill()
            self.client.wait(timeout=30)
        if self.server is not None:
            self.server.stop()
            self.server = None
        shutil.rmtree(self.tmp, ignore_errors=True)


def _quiet():
    """Profiler options: device and runtime events, no Python tracer (it
    would slow every Python call of the server)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


# ------------------------------------------------------------------ check
def check(samples, requests_by_key, frames, dets, cfg) -> dict:
    """Compare each sampled answer with the reference: its region keys
    exactly, its pixels by the widest gap (a block may break an encoder
    rounding tie either way; see ``reference.encode_block_ties``)."""
    import reference

    arch = reference.Archive(frames, dets, cfg["gop"], cfg["qp"])
    mismatched, keys = 0, []
    pairs = []
    for key, regions in samples:
        q = requests_by_key[json.dumps(key)]
        want = Counter(arch.regions(q["label"], q["lo"], q["hi"]))
        got = Counter((f, box) for f, box, _ in regions)
        mismatched += sum(((want - got) + (got - want)).values())
        wanted = set(want)
        for f, box, px in regions:
            if (f, box) in wanted:
                keys.append((f, box))
                pairs.append((key, f, box, px))
    arch.prepare(keys)
    limit = cfg["check"]["pixel_gap_limit"]
    gap, n_px, ties, worst = 0.0, 0, 0, None
    for key, f, box, px in pairs:
        if px.shape != (box[2] - box[0], box[3] - box[1]):
            mismatched += 1
            continue
        g, t = arch.gap(f, box, px, limit)
        ties += t
        n_px += px.size
        if g > gap:
            gap = g
            worst = {"request": key, "frame": f, "box": list(box)}
    return {"mismatched": mismatched, "gap": gap, "regions": len(pairs),
            "pixels": n_px, "ties": ties, "worst": worst}


# -------------------------------------------------------------------- run
def job_for(spec: dict, cfg: dict, seed: int, seconds: float) -> tuple:
    """(load-generator job, requests by key) for the cell's mix."""
    mix = spec["mix"]
    k = int(mix.get("sample", 8))
    if mix["arrivals"]["kind"] == "closed":
        per_client = 2_000
        clients = traffic.closed_loop(mix, cfg, seed, per_client)
        sample = traffic.closed_sample(len(clients), k, seed)
        by_key = {json.dumps([c, s]): q for c, qs in enumerate(clients)
                  for s, q in enumerate(qs[:traffic.CLOSED_SAMPLE_AMONG])}
        job = {"loop": "closed", "clients": clients, "sample": sample}
    else:
        reqs = traffic.open_loop(mix, cfg, seed, seconds)
        by_key = {json.dumps(r["i"]): r for r in reqs}
        job = {"loop": "open", "requests": reqs,
               "sample": traffic.open_sample(reqs, k, seed)}
    return dict(job, seconds=seconds, grace=GRACE_S), by_key


def prepare_env(rehearse: bool) -> None:
    """Before JAX starts: the compile cache at a fixed path in the checkout
    (unless JAX_COMPILATION_CACHE_DIR names one), every program cached."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(HERE / ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, str(SRC))


def run(args) -> int:
    spec = load_cell(args.workload)
    seed = args.seed % (1 << 64)
    prepare_env(args.rehearse)

    h = Harness(spec, seed, args.rehearse)
    trace_dir = tempfile.mkdtemp(prefix="tbtrace") if args.trace else None
    try:
        device = h.start()
        cfg = h.cfg
        job, by_key = job_for(spec, cfg, seed, args.seconds)
        h.spawn_client(job)
        t = time.perf_counter()
        frames, dets = h.ingest()
        log(f"archive: {cfg['n_frames']} frames {cfg['width']}x"
            f"{cfg['height']}, seed {seed}; made and ingested in "
            f"{time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        n_shapes = h.warm_shapes(spec["mix"])
        log(f"warm-up: {n_shapes} decode stream shapes in "
            f"{time.perf_counter() - t:.3f} s")
        mix = spec["mix"]
        if mix.get("warm_fill"):
            t = time.perf_counter()
            cat = traffic.catalogue(mix["queries"], cfg["n_frames"], seed)
            h.warm_fill(cat[:mix["warm_fill"]])
            log(f"warm fill: {mix['warm_fill']} catalogue queries in "
                f"{time.perf_counter() - t:.3f} s")
        h.wait_ready()
        setup_s = time.perf_counter() - T_START
        log(f"setup_s {setup_s}")
        win = h.window(args.seconds, trace_dir)
        records, samples = h.collect()
        device["memory_peak_bytes"] = h.memory_peak()
        h.close()           # the program's state is freed before the check
        t = time.perf_counter()
        result = check(samples, by_key, frames, dets, cfg)
        log(f"check: {result['regions']} regions, {result['pixels']} pixels "
            f"against the reference in {time.perf_counter() - t:.3f} s; "
            f"{result['ties']} blocks broke an encoder rounding tie the "
            f"other way; widest gap at {json.dumps(result['worst'])}")
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 3
    finally:
        h.close()

    ctx = types.SimpleNamespace(
        records=records, t0=win["t0"], seconds=args.seconds,
        grace=GRACE_S, setup_s=setup_s, device_kind=device["kind"],
        pixels_in_window=win["pixels_in_window"],
        bytes_per_pixel=BYTES_PER_PIXEL, trace=None)
    breakdown = None
    if trace_dir is not None:
        import trace_reduce

        doc = trace_reduce.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if args.keep_trace:
            pathlib.Path(args.keep_trace).write_text(json.dumps(doc))
        ctx.trace = trace_reduce.reduce(doc, DECODE_PROGRAM)
        if ctx.trace is not None:
            device["busy_s"] = ctx.trace["busy_s"]
            device["window_s"] = ctx.trace["window_s"]
            breakdown = {"device_ops": ctx.trace["top_ops"],
                         "idle_gaps": ctx.trace["gaps"]}
    metrics = {}
    for m in spec["per_layer" if args.trace else "end_to_end"]:
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    lost = sum(1 for r in records if "done" not in r)
    failed = lost + sum(1 for r in records if "error" in r)
    limit = cfg["check"]["pixel_gap_limit"]
    checks = {"pixel_gap": {"value": result["gap"], "limit": limit},
              "region_key_mismatches": {"value": result["mismatched"],
                                        "limit": 0},
              "requests_never_answered": {"value": lost, "limit": 0},
              "compiles_in_window": {"value": h.compiles, "limit": 0}}
    correct = (result["gap"] <= limit and result["mismatched"] == 0
               and lost == 0 and h.compiles == 0 and result["regions"] > 0)
    sampled = {json.dumps(key) for key, _ in samples}
    done = [r for r in records if "stats" in r]
    late = sorted(r["sent"] - (win["t0"] + r["due"]) for r in records)
    log(f"offered: {len(records)} requests in {args.seconds} s "
        f"({len(records) / args.seconds:.3f}/s), {mix['arrivals']}")
    log(f"answered {len(done)}, failed {failed} (never answered {lost}); "
        f"latency percentiles are over all {len(records)}")
    if late:
        log(f"generator lateness: median {late[len(late) // 2] * 1e3:.3f} "
            f"ms, max {late[-1] * 1e3:.3f} ms")
    log(f"compiles_in_window {h.compiles}")
    # a hit is a tile from the tile cache or from another request's decode
    # in the same merged batch
    served = Counter("+".join(k for k in ("hits", "misses")
                              if r["stats"]["cache_" + k]) or "no tiles"
                     for r in records if "stats" in r
                     and json.dumps(r["key"]) in sampled)
    log(f"checked replies by how their tiles were fetched: {dict(served)}")
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    line = {"correct": bool(correct), "attempted": len(records),
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at 96x160 (no chip needed)")
    ap.add_argument("--keep-trace", metavar="PATH",
                    help="with --trace 1, also write the compact trace "
                         "document there")
    return ap.parse_args(argv)


if __name__ == "__main__":
    raise SystemExit(run(parse_args()))
