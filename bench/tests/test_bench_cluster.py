"""A cluster cell rehearses on the CPU: four node processes behind the
program's router, the load generator on the router, the read-back from
every replica; a replica that serves wrong pixels, or a node that cannot
start, fails the run.

Each test runs ``bench/run.py`` on the cell as ``BENCHMARK.json`` has it.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
CELL = "vr2k-k2x4-select"


def _run(*args, timeout=180):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(BENCH / ".jax_cache"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELL,
                        "--seconds", "1", "--rehearse", *args], cwd=ROOT,
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    # the harness logs to stdout ("# " lines) before the result line
    return p.returncode, line, p.stderr + p.stdout


def test_traced_rehearsal_is_correct_on_every_replica():
    rc, line, err = _run("--seed", "2147483659", "--trace", "1")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    checks = line["checks"]
    assert checks["requests_never_answered"]["value"] == 0
    assert checks["replica_reads_failed"]["value"] == 0
    assert checks["replica_region_key_mismatches"]["value"] == 0
    assert line["device"]["count"] == 4
    # the router's and the nodes' spans and counters; a CPU host has no
    # device plane, so the idle share is left out
    assert {"relay_ms.k2x4", "marshal_ms.k2x4", "lookup_ms.k2x4",
            "decode_ms.k2x4", "node_skew.k2x4"} == set(line["metrics"])
    assert line["metrics"]["node_skew.k2x4"]["value"] >= 1.0


def test_corrupted_replica_is_caught():
    # one SOT of one replica, the last GOP of a camera, is corrupted: the
    # read-back of every GOP from every replica has to find it
    rc, line, err = _run("--seed", "21", "--trace", "0",
                         "--node-fault", "corrupt_sot")
    assert rc == 0, err[-3000:]
    assert line["correct"] is False
    c = line["checks"]["replica_pixel_gap"]
    assert c["value"] > c["limit"]
    assert '"node": "n0"' in err.split("# check replica:")[1].splitlines()[0]


def test_read_back_covers_every_gop_label_and_camera():
    import cluster
    import run

    spec = run.load_cell(CELL)
    cfg = spec["config"]
    qs = cluster.read_back_queries(spec["mix"], cfg)
    gop, n = cfg["gop"], spec["mix"]["queries"]["length_range"][0]
    cover = {(q["camera"], q["lo"] // gop, q["label"]) for q in qs}
    assert cover == {(c, g, lab) for c in range(cfg["cluster"]["cameras"])
                     for g in range(cfg["n_frames"] // gop)
                     for lab in spec["mix"]["queries"]["labels"]}
    # each ends its GOP, so it decodes the GOP's every frame
    assert all(q["hi"] % gop == 0 and q["hi"] - q["lo"] == n for q in qs)


def test_node_failing_at_start_fails_the_run():
    rc, line, err = _run("--seed", "21", "--trace", "0",
                         "--node-fault", "start")
    assert rc not in (0, 3), err[-3000:]
    assert line is None
    assert "planted fault" in err


def test_line_trace_sums_kernel_time_and_labels_the_busiest_node():
    import cluster

    def trace(busy, kernel, op):
        return {"busy_s": busy, "window_s": 45.0, "kernel_s": kernel,
                "top_ops": [[op, kernel]], "gaps": [["tasm.marshal", 1.0]]}

    t = cluster._busiest([trace(1.0, 0.5, "a"), trace(3.0, 1.5, "b")])
    assert t["busy_s"] == 2.0 and t["window_s"] == 45.0
    # summed like the nodes' decoded pixels that a roofline divides by it
    assert t["kernel_s"] == 2.0
    assert t["top_ops"] == [["n1 b", 1.5]]
    assert t["gaps"] == [["n1 tasm.marshal", 1.0]]
    assert cluster._busiest([trace(1.0, 0.5, "a"), None]) is None
