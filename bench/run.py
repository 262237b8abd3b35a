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

A configuration with a ``cluster`` block is served instead by node
processes, one chip each, behind the program's router in this process,
which then holds no chip (``cluster.py``).

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
import functools  # noqa: E402
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
#: faults node.py can plant in a cluster node (--fault)
NODE_FAULTS = ("start", "corrupt_sot", "altered", "high")
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


def detections(dets) -> list:
    """The corpus's detections as the program takes them."""
    return [[(lab, tuple(int(v) for v in box)) for lab, box in d]
            for d in dets]


def encoding(cfg: dict, dets: list) -> dict:
    """The ingest keywords of an archive with these detections: encoder,
    tiling policy and, for KQKO, the layouts it chooses at ingest from the
    detections alone, so that each tile is encoded once from the source
    frames."""
    from repro.codec.encode import EncoderConfig
    from repro.core import KQKOPolicy, NoTilingPolicy, SemanticIndex

    enc = EncoderConfig(gop=cfg["gop"], qp=cfg["qp"])
    lay = cfg["layout"]
    if lay["policy"] != "kqko":
        return {"detections": dets, "initial_layouts": None, "encoder": enc,
                "policy": NoTilingPolicy()}
    policy = KQKOPolicy(lay["query_objects"])
    index = SemanticIndex()
    for f, d in enumerate(dets):
        for lab, box in d:
            index.add(VIDEO, f, lab, box)
    gop, n = cfg["gop"], cfg["n_frames"]
    sots = [types.SimpleNamespace(sot_id=s, frame_start=s * gop,
                                  frame_end=(s + 1) * gop)
            for s in range(n // gop)]
    shim = types.SimpleNamespace(sots=sots, encoder=enc)
    layouts = policy.on_ingest(index, shim, VIDEO,
                               (cfg["height"], cfg["width"]))
    return {"detections": dets, "initial_layouts": layouts, "encoder": enc,
            "policy": policy}


def warm_shapes(ts, cfg: dict, mix: dict) -> int:
    """Decode once at every (depth bucket, column bucket) that the mix's
    requests can hand the batched decode of the tile store ``ts``: each
    call is one stream of exactly that shape.  Whole-GOP scans decode every
    block of a SOT at the GOP's depth, one shape; selections start
    anywhere, so they reach every depth up to their longest range and,
    merged, any column count up to a SOT's.  Returns the number of
    shapes."""
    rec = ts.sots[0]
    cells = [(t, b) for t in range(rec.layout.n_tiles)
             for b in range(rec.layout.tile_blocks(t))]
    gop = cfg["gop"]
    if mix["queries"]["kind"] == "gop_cycle":
        depths, buckets = [gop], [len(cells)]
    else:
        top = min(traffic.longest(mix["queries"]), gop)
        depths = [min(d, gop) for d in DEPTH_BUCKETS if d < 2 * top]
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
                            blocks={t: tuple(v) for t, v in masks.items()})
            n += 1
    return n


# --------------------------------------------------------- load generator
def spawn_loadgen(job: dict) -> subprocess.Popen:
    """Start the load generator (``loadgen.py``, on the CPU) and hand it
    ``job``, which names the address it connects to."""
    client = subprocess.Popen(
        [sys.executable, str(HERE / "loadgen.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    client.stdin.write((json.dumps(dict(job, src=str(SRC))) + "\n").encode())
    client.stdin.flush()
    return client


def wait_ready(client: subprocess.Popen) -> None:
    line = client.stdout.readline()
    if line.strip() != b"ready":
        raise RuntimeError(f"load generator did not start: {line!r}")


def start_window(client: subprocess.Popen, t0: float) -> None:
    client.stdin.write((json.dumps({"t0": t0}) + "\n").encode())
    client.stdin.flush()


def collect(client: subprocess.Popen) -> tuple[list, list]:
    """The load generator's records and sampled answers."""
    import numpy as np

    out = client.stdout
    head = json.loads(out.readline())
    blob = out.read(head["blob_bytes"])
    rc = client.wait(timeout=60)
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


def stop_process(proc) -> None:
    if proc is not None and proc.poll() is None:
        proc.kill()
        proc.wait(timeout=30)


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
        self.client = spawn_loadgen(dict(
            job, addr=self.addr, video=VIDEO,
            max_frame_bytes=self.cfg["serving"]["max_frame_mb"] << 20))

    def ingest(self) -> tuple:
        """Make the archive from the seed and ingest it; (frames, dets)."""
        import corpus

        frames, dets = corpus.generate(self.cfg, self.seed)
        dets_t = detections(dets)
        self.store.ingest(VIDEO, frames, **encoding(self.cfg, dets_t))
        return frames, dets_t

    def warm_shapes(self, mix: dict) -> int:
        return warm_shapes(self.store.video(VIDEO).store, self.cfg, mix)

    def warm_fill(self, queries: list) -> None:
        """Ask each query once, least popular first, so the most popular
        are the most recent (set-up the traffic needs: a dashboard's cache
        is warm)."""
        for q in reversed(queries):
            self.store.scan(VIDEO).labels(q["label"]).frames(
                q["lo"], q["hi"]).execute()

    def wait_ready(self) -> None:
        wait_ready(self.client)

    # -- window -----------------------------------------------------------
    def window(self, seconds: float, trace_dir: str | None) -> dict:
        """Offer the mix for ``seconds``; counters around the window."""
        import jax

        pixels = self.store.stats()["pixels_decoded_total"]
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir, profiler_options=_quiet())
        t0 = time.monotonic() + 0.2
        start_window(self.client, t0)
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
        return collect(self.client)

    def memory_peak(self) -> int:
        import jax

        st = jax.devices()[0].memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0))

    def close(self) -> None:
        stop_process(self.client)
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
def compare(answers, archives: dict, limit: float, groups) -> dict:
    """Compare answers with the reference: region keys exactly, pixels by
    the widest gap (a block may break an encoder rounding tie either way;
    see ``reference.encode_block_ties``).  ``answers``: ``(group, where,
    camera, query, regions)``; ``archives``: camera -> ``reference.Archive``.
    Returns, for each of ``groups``, its mismatched keys, widest gap,
    regions and pixels compared, ties broken and where the widest gap
    lies."""
    out = {g: {"mismatched": 0, "gap": 0.0, "regions": 0, "pixels": 0,
               "ties": 0, "worst": None} for g in groups}
    keys: dict = {}
    pairs = []
    for group, where, cam, q, regions in answers:
        r = out[group]
        want = Counter(archives[cam].regions(q["label"], q["lo"], q["hi"]))
        got = Counter((f, box) for f, box, _ in regions)
        r["mismatched"] += sum(((want - got) + (got - want)).values())
        wanted = set(want)
        for f, box, px in regions:
            if (f, box) in wanted:
                keys.setdefault(cam, []).append((f, box))
                pairs.append((r, where, cam, f, box, px))
    for cam, k in keys.items():
        archives[cam].prepare(k)
    for r, where, cam, f, box, px in pairs:
        r["regions"] += 1
        if px.shape != (box[2] - box[0], box[3] - box[1]):
            r["mismatched"] += 1
            continue
        g, t = archives[cam].gap(f, box, px, limit)
        r["ties"] += t
        r["pixels"] += px.size
        if g > r["gap"]:
            r["gap"] = g
            r["worst"] = dict(where, frame=f, box=list(box))
    return out


def sampled_answers(samples, requests_by_key) -> list:
    """The load generator's sampled replies as ``compare`` answers."""
    out = []
    for key, regions in samples:
        q = requests_by_key[json.dumps(key)]
        out.append(("sample", {"request": key}, q.get("camera", 0), q,
                    regions))
    return out


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


def prepare_env(rehearse: bool, holds_chip: bool = True) -> None:
    """Before JAX starts: the compile cache at a fixed path in the checkout
    (unless JAX_COMPILATION_CACHE_DIR names one), every program cached; a
    process that holds no chip, or a rehearsal, on the CPU."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(HERE / ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    if rehearse or not holds_chip:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, str(SRC))


def measure(args, spec: dict, seed: int) -> dict:
    """One run of a one-chip cell: set-up, window, check.  Returns what
    ``report`` reads."""
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
            cat = traffic.catalogue(mix["queries"], cfg, seed)
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
        import reference

        arch = reference.Archive(frames, dets, cfg["gop"], cfg["qp"])
        result = compare(sampled_answers(samples, by_key), {0: arch},
                         cfg["check"]["pixel_gap_limit"], ["sample"])
        result = result["sample"]
        log(f"check: {result['regions']} regions, {result['pixels']} pixels "
            f"against the reference in {time.perf_counter() - t:.3f} s; "
            f"{result['ties']} blocks broke an encoder rounding tie the "
            f"other way; widest gap at {json.dumps(result['worst'])}")
    finally:
        h.close()
    trace = None
    if trace_dir is not None:
        import trace_reduce

        doc = trace_reduce.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if args.keep_trace:
            pathlib.Path(args.keep_trace).write_text(json.dumps(doc))
        trace = trace_reduce.reduce(doc, DECODE_PROGRAM)
    return {"cfg": cfg, "records": records, "samples": samples,
            "result": result, "t0": win["t0"], "setup_s": setup_s,
            "device": device, "compiles": h.compiles, "trace": trace,
            "ctx": {"pixels_in_window": win["pixels_in_window"]},
            "checks": {}}


def report(args, spec: dict, out: dict) -> None:
    """The cell's metrics, checks and result line, from what ``measure``
    (or the cluster's) returned."""
    cfg, records, result = out["cfg"], out["records"], out["result"]
    device, trace, compiles = out["device"], out["trace"], out["compiles"]
    ctx = types.SimpleNamespace(
        records=records, t0=out["t0"], seconds=args.seconds,
        grace=GRACE_S, setup_s=out["setup_s"], device_kind=device["kind"],
        bytes_per_pixel=BYTES_PER_PIXEL, trace=trace, **out["ctx"])
    breakdown = None
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        breakdown = {"device_ops": trace["top_ops"],
                     "idle_gaps": trace["gaps"]}
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
              "compiles_in_window": {"value": compiles, "limit": 0},
              **out["checks"]}
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and result["regions"] > 0)
    mix = spec["mix"]
    sampled = {json.dumps(key) for key, _ in out["samples"]}
    done = [r for r in records if "stats" in r]
    late = sorted(r["sent"] - (out["t0"] + r["due"]) for r in records)
    log(f"offered: {len(records)} requests in {args.seconds} s "
        f"({len(records) / args.seconds:.3f}/s), {mix['arrivals']}")
    log(f"answered {len(done)}, failed {failed} (never answered {lost}); "
        f"latency percentiles are over all {len(records)}")
    if late:
        log(f"generator lateness: median {late[len(late) // 2] * 1e3:.3f} "
            f"ms, max {late[-1] * 1e3:.3f} ms")
    log(f"compiles_in_window {compiles}")
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


def run(args) -> int:
    spec = load_cell(args.workload)
    seed = args.seed % (1 << 64)
    if "cluster" in spec["config"]:
        import cluster

        # this process serves the router and holds no chip: each node
        # process holds one
        prepare_env(args.rehearse, holds_chip=False)
        go = functools.partial(cluster.measure, this=sys.modules[__name__],
                               node_fault=args.node_fault)
    else:
        prepare_env(args.rehearse)
        go = measure
    try:
        out = go(args, spec, seed)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 3
    report(args, spec, out)
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
    ap.add_argument("--node-fault", choices=NODE_FAULTS,
                    help="plant a fault in the first node of a cluster "
                         "cell (see node.py; for the tests)")
    return ap.parse_args(argv)


if __name__ == "__main__":
    raise SystemExit(run(parse_args()))
