"""A cluster cell's run: node processes, one chip each, behind the
program's router, which this process serves.

A configuration with a ``cluster`` block (``nodes``, ``replication``,
``cameras``) deploys ``nodes`` node processes (``node.py``), one a chip of
the cell, node *i* confined to chip *i* the way a deployment confines
``scripts/tasm_serve.py`` (``TPU_VISIBLE_CHIPS`` and the process bounds), each
serving its own ``VideoStore`` on a Unix socket.  This process holds no
chip: it serves the program's ``ClusterRouter`` over the nodes, at
``replication`` replicas a camera, from a ``ClusterRouterServer`` on a socket
of its own, and the load generator connects to that.  Set-up makes camera
*c*'s archive from ``[seed, c]`` and ingests it through the router, so every
replica is written; each node then warms its decode programs once (they are
keyed by qp and stream shape, not by camera).  Each node records its own
window: compiles, decoded pixels, span records and, with ``--trace 1``, a
profiler trace.  After the window the harness reads the end of every GOP of
each camera, for each label of the mix, from every replica's node directly,
not through the router, and checks those answers with the sampled replies
against the reference.

Only the device check -- a node whose JAX finds no TPU, or not exactly one
chip -- raises ``NoChip``.  A node that fails to build, dies or hangs fails
the run.
"""
from __future__ import annotations

import json
import os
import pathlib
import queue
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

HERE = pathlib.Path(__file__).resolve().parent
#: seconds a node may take to start (JAX reaching its chip, the store and
#: server built) and to answer a command
NODE_START_S = 600.0
NODE_REPLY_S = 900.0
#: regions the read-back takes at least from each replica of a camera
READ_BACK_MIN_REGIONS = 4


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def read_back_queries(mix: dict, cfg: dict) -> list[dict]:
    """What the read-back asks each replica of every camera: for each GOP
    and each label of the mix, the GOP's last frames, as many as the
    mix's shortest query has.  A read at a GOP's end decodes every frame
    of the GOP's selected blocks on the node, so a replica that lost or
    altered any SOT of a camera answers one of them wrongly."""
    q = mix["queries"]
    n, gop, gops = q["length_range"][0], cfg["gop"], cfg["n_frames"] // cfg["gop"]
    return [{"camera": c, "label": label, "lo": (g + 1) * gop - n,
             "hi": (g + 1) * gop}
            for c in range(cfg["cluster"]["cameras"])
            for g in range(gops) for label in sorted(q["labels"])]


class Node:
    """One node process and its command pipe (a JSON line each way)."""

    def __init__(self, index: int, workload: str, sock: str,
                 rehearse: bool, fault: str | None):
        self.name, self.sock = f"n{index}", sock
        env = dict(os.environ, JAX_PLATFORMS="cpu" if rehearse else "tpu")
        if not rehearse:
            port = _free_port()
            env.update(TPU_VISIBLE_CHIPS=str(index),
                       TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                       TPU_PROCESS_BOUNDS="1,1,1",
                       TPU_PROCESS_PORT=str(port),
                       TPU_PROCESS_ADDRESSES=f"localhost:{port}")
        cmd = [sys.executable, str(HERE / "node.py"), "--workload", workload,
               "--socket", sock] + ["--rehearse"] * rehearse \
            + (["--fault", fault] if fault else [])
        self.proc = subprocess.Popen(cmd, env=env, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE)
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._read, daemon=True,
                         name=f"{self.name}-out").start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def reply(self, timeout: float) -> dict:
        try:
            line = self._lines.get(timeout=timeout)
        except queue.Empty:
            raise RuntimeError(f"node {self.name} gave no answer in "
                               f"{timeout:.0f} s") from None
        if line is None:
            raise RuntimeError(f"node {self.name} exited "
                               f"{self.proc.wait()}")
        return json.loads(line)

    def send(self, op: str, **kw) -> None:
        self.proc.stdin.write((json.dumps(dict(kw, op=op)) + "\n").encode())
        self.proc.stdin.flush()

    def ask(self, op: str, **kw) -> dict:
        self.send(op, **kw)
        return self.reply(NODE_REPLY_S)

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.ask("stop")
                self.proc.wait(timeout=60)
            except (RuntimeError, OSError, subprocess.TimeoutExpired):
                pass
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)


def _all(nodes, fn) -> list:
    """``fn(node)`` on every node at once; results in node order."""
    with ThreadPoolExecutor(len(nodes)) as pool:
        return list(pool.map(fn, nodes))


class Cluster:
    """The nodes, the router and its server, for one run."""

    def __init__(self, spec: dict, seed: int, rehearse: bool, this,
                 node_fault: str | None):
        self.spec, self.seed, self.rehearse, self.run = (spec, seed,
                                                         rehearse, this)
        self.cfg = this.rehearsal_size(spec["config"]) if rehearse \
            else spec["config"]
        self.layout = self.cfg["cluster"]
        self.videos = [f"cam{c}" for c in range(self.layout["cameras"])]
        self.max_frame_bytes = self.cfg["serving"]["max_frame_mb"] << 20
        self.node_fault = node_fault
        self.tmp = tempfile.mkdtemp(prefix="tc")
        if len(self.tmp) > 90:      # past what a Unix socket path holds
            shutil.rmtree(self.tmp)
            (HERE / ".cache").mkdir(exist_ok=True)
            self.tmp = os.path.relpath(tempfile.mkdtemp(
                prefix="tc", dir=HERE / ".cache"))
        self.addr = os.path.join(self.tmp, "router")
        self.nodes: list[Node] = []
        self.server = None
        self.client = None

    # -- set-up -----------------------------------------------------------
    def start(self) -> dict:
        """Start the nodes, check their devices, start the router; the
        device block."""
        n = self.layout["nodes"]
        if n != self.spec["cell"]["chips"]:
            raise ValueError(f"{n} nodes on {self.spec['cell']['chips']} "
                             f"chips: a node holds one chip")
        for i in range(n):
            self.nodes.append(Node(
                i, self.spec["cell"]["name"],
                os.path.join(self.tmp, f"n{i}"), self.rehearse,
                self.node_fault if i == 0 else None))
        answers = _all(self.nodes, lambda nd: nd.reply(NODE_START_S))
        devs = [a.get("device") for a in answers]
        self.run.log("node devices: " + json.dumps(
            {nd.name: a for nd, a in zip(self.nodes, answers)}))
        if not self.rehearse and not all(
                d and d["platform"] == "tpu" and d["count"] == 1
                for d in devs):
            # a TPU chip opens in one process at a time, so four nodes
            # that each hold one TPU hold four chips
            raise self.run.NoChip(
                f"cell wants one TPU chip a node on {n} nodes; the nodes "
                f"see {[a.get('device') or a.get('no_device') for a in answers]}")
        from repro.core import ClusterRouter, ClusterRouterServer

        self.router = ClusterRouter(
            {nd.name: nd.sock for nd in self.nodes},
            replication=self.layout["replication"],
            max_frame_bytes=self.max_frame_bytes)
        sv = self.cfg["serving"]
        self.server = ClusterRouterServer(
            self.router, path=self.addr, transport=sv["transport"],
            max_batch=sv["max_batch"],
            max_frame_bytes=self.max_frame_bytes).start()
        return {"platform": devs[0]["platform"], "kind": devs[0]["kind"],
                "count": sum(d["count"] for d in devs)}

    def spawn_client(self, job: dict) -> None:
        self.client = self.run.spawn_loadgen(dict(
            job, addr=self.addr, videos=self.videos,
            max_frame_bytes=self.max_frame_bytes))

    def ingest(self) -> dict:
        """Make each camera's archive from ``[seed, camera]`` and ingest it
        through the router, the cameras at once; camera -> (frames,
        detections)."""
        import corpus

        # placed in camera order, not in the order the ingests land, so
        # every run places the cameras alike
        for v in self.videos:
            self.router.placement.place(v)

        def one(c: int):
            frames, dets = corpus.generate(self.cfg, [self.seed, c])
            dets = self.run.detections(dets)
            self.router.ingest(self.videos[c], frames,
                               **self.run.encoding(self.cfg, dets))
            return frames, dets

        with ThreadPoolExecutor(len(self.videos)) as pool:
            return dict(enumerate(pool.map(one, range(len(self.videos)))))

    def warm(self) -> int:
        """Each node decodes once at every stream shape of the mix."""
        return sum(a["shapes"] for a in _all(
            self.nodes, lambda nd: nd.ask("warm")))

    def placement(self) -> dict:
        return {v: list(self.router.placement.nodes_for(v))
                for v in self.videos}

    # -- window -----------------------------------------------------------
    def window(self, seconds: float, trace_dir: str | None) -> dict:
        """Offer the mix for ``seconds``; each node's counters and span
        records of the window."""
        if trace_dir is not None:
            _all(self.nodes, lambda nd: nd.ask(
                "trace", dir=os.path.join(trace_dir, nd.name)))
        t0 = time.monotonic() + 0.5
        for nd in self.nodes:
            nd.send("window", t0=t0, seconds=seconds)
        self.run.start_window(self.client, t0)
        wins = _all(self.nodes, lambda nd: nd.reply(seconds + NODE_REPLY_S))
        return {"t0": t0, "nodes": wins}

    def read_back(self) -> tuple[list, int]:
        """Read ``read_back_queries`` from every replica's node directly.
        Returns the answers for ``compare`` and the reads that failed: a
        read that raised, a replica that returned fewer than
        ``READ_BACK_MIN_REGIONS`` regions of a camera in all, and a replica
        the configuration asks for that the placement lacks."""
        from repro.core import RemoteVideoStore

        socks = {nd.name: nd.sock for nd in self.nodes}
        queries = read_back_queries(self.spec["mix"], self.cfg)
        asks: dict = {}
        failed = 0
        for c, video in enumerate(self.videos):
            reps = self.router.placement.nodes_for(video)
            failed += max(0, self.layout["replication"] - len(set(reps)))
            for name in reps:
                asks.setdefault(name, []).append(c)
        answers = []
        for name, cams in asks.items():
            try:
                st = RemoteVideoStore(socks[name], transport="shm",
                                      want_plans=False,
                                      max_frame_bytes=self.max_frame_bytes)
            except Exception as e:  # noqa: BLE001 - counted, logged
                self.run.log(f"read back from {name}: {type(e).__name__}: "
                             f"{e}")
                failed += sum(q["camera"] in cams for q in queries)
                continue
            with st:
                for c in cams:
                    video, got = self.videos[c], 0
                    for q in (q for q in queries if q["camera"] == c):
                        try:
                            res = st.scan(video).labels(q["label"]).frames(
                                q["lo"], q["hi"]).execute()
                        except Exception as e:  # noqa: BLE001 - counted
                            self.run.log(f"read back {video} from {name}: "
                                         f"{type(e).__name__}: {e}")
                            failed += 1
                            continue
                        regions = [(int(f), tuple(int(v) for v in box),
                                    px.copy()) for f, box, px in res.regions]
                        got += len(regions)
                        answers.append(("replica",
                                        {"video": video, "node": name,
                                         "label": q["label"], "lo": q["lo"]},
                                        c, q, regions))
                    if got < READ_BACK_MIN_REGIONS:
                        failed += 1
        return answers, failed

    def memory_peaks(self) -> list[int]:
        return [a["peak_bytes"] for a in _all(
            self.nodes, lambda nd: nd.ask("memory"))]

    def close(self) -> None:
        self.run.stop_process(self.client)
        if self.server is not None:
            self.server.stop()      # the router first: no node vanishes
            self.server = None      # under it
        if self.nodes:
            _all(self.nodes, Node.stop)
        self.nodes = []
        shutil.rmtree(self.tmp, ignore_errors=True)


def _traces(trace_dir: str, names: list, keep: str | None,
            program: str) -> list:
    """Each node's reduced trace (``None`` where it holds no device op)."""
    import trace_reduce

    docs = [trace_reduce.extract(os.path.join(trace_dir, n)) for n in names]
    if keep:
        pathlib.Path(keep).write_text(json.dumps(dict(zip(names, docs))))
    return [trace_reduce.reduce(d, program) for d in docs]


def measure(args, spec: dict, seed: int, *, this, node_fault=None) -> dict:
    """One run of a cluster cell: set-up, window, read-back, check.
    ``this`` is the ``run`` module.  Returns what ``run.report`` reads."""
    log = this.log
    c = Cluster(spec, seed, args.rehearse, this, node_fault)
    trace_dir = tempfile.mkdtemp(prefix="tbtrace") if args.trace else None
    try:
        device = c.start()
        cfg = c.cfg
        job, by_key = this.job_for(spec, cfg, seed, args.seconds)
        c.spawn_client(job)
        t = time.perf_counter()
        archives = c.ingest()
        log(f"placement: {json.dumps(c.placement())}")
        log(f"archive: {len(c.videos)} cameras of {cfg['n_frames']} frames "
            f"{cfg['width']}x{cfg['height']}, seed {seed}; made and "
            f"ingested through the router in "
            f"{time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        n_shapes = c.warm()
        log(f"warm-up: {n_shapes} decode stream shapes over "
            f"{len(c.nodes)} nodes in {time.perf_counter() - t:.3f} s")
        this.wait_ready(c.client)
        setup_s = time.perf_counter() - this.T_START
        log(f"setup_s {setup_s}")
        win = c.window(args.seconds, trace_dir)
        records, samples = this.collect(c.client)
        t = time.perf_counter()
        replica_answers, read_failed = c.read_back()
        log(f"read back from each replica in "
            f"{time.perf_counter() - t:.3f} s")
        peaks = c.memory_peaks()
        log("memory peak by node: " + ", ".join(
            f"{nd.name} {p}" for nd, p in zip(c.nodes, peaks)))
        device["memory_peak_bytes"] = max(peaks)
        names = [nd.name for nd in c.nodes]
        c.close()           # the program's state is freed before the check
        import reference

        t = time.perf_counter()
        refs = {cam: reference.Archive(fr, dets, cfg["gop"], cfg["qp"])
                for cam, (fr, dets) in archives.items()}
        limit = cfg["check"]["pixel_gap_limit"]
        res = this.compare(this.sampled_answers(samples, by_key)
                           + replica_answers, refs, limit,
                           ["sample", "replica"])
        for g, r in res.items():
            log(f"check {g}: {r['regions']} regions, {r['pixels']} pixels; "
                f"{r['ties']} blocks broke an encoder rounding tie the "
                f"other way; widest gap at {json.dumps(r['worst'])}")
        log(f"checked against the reference in "
            f"{time.perf_counter() - t:.3f} s")
    finally:
        c.close()
    traces = None
    if trace_dir is not None:
        traces = _traces(trace_dir, names, args.keep_trace,
                         this.DECODE_PROGRAM)
        shutil.rmtree(trace_dir, ignore_errors=True)
    nodes = win["nodes"]
    rep = res["replica"]
    return {
        "cfg": cfg, "records": records, "samples": samples,
        "result": res["sample"], "t0": win["t0"], "setup_s": setup_s,
        "device": device, "compiles": sum(w["compiles"] for w in nodes),
        "trace": _busiest(traces),
        "ctx": {"pixels_in_window": sum(w["pixels"] for w in nodes),
                "node_pixels": [w["pixels"] for w in nodes],
                "node_spans": [w["spans"] for w in nodes],
                "node_traces": traces},
        "checks": {"replica_pixel_gap": {"value": rep["gap"],
                                         "limit": limit},
                   "replica_region_key_mismatches": {
                       "value": rep["mismatched"], "limit": 0},
                   "replica_reads_failed": {"value": read_failed,
                                            "limit": 0}}}


def _busiest(traces):
    """The line's trace.  ``busy_s`` and ``window_s`` are the means over
    the nodes, as a run's device block reports them; ``kernel_s`` is the
    sum over the nodes, like the window's decoded pixels
    (``ctx.pixels_in_window``) that a roofline divides by it; ``top_ops``
    and ``gaps`` are those of the node with the most device time, each
    name prefixed with that node's."""
    if not traces or any(t is None for t in traces):
        return None
    i = max(range(len(traces)), key=lambda k: traces[k]["busy_s"])
    n = len(traces)
    return {"busy_s": sum(t["busy_s"] for t in traces) / n,
            "window_s": sum(t["window_s"] for t in traces) / n,
            "kernel_s": sum(t["kernel_s"] for t in traces),
            "top_ops": [[f"n{i} {name}", v]
                        for name, v in traces[i]["top_ops"]],
            "gaps": [[f"n{i} {name}", v] for name, v in traces[i]["gaps"]]}
