"""The harness takes cells as data, and each cell rehearses on the CPU.

Every ``workloads`` entry of ``BENCHMARK.json`` resolves by name to its
configuration file, traffic file and metric readers; a new configuration
file plus a new traffic file, with entries added to a copy of
``BENCHMARK.json``, make a cell that runs without any existing file being
edited; every cell's rehearsal prints the contract's last line; and without
``--rehearse`` a host with no chip gets no result.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

KEYS = ("correct", "attempted", "failed", "metrics", "device")


def _run(cwd, *args, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(BENCH / ".jax_cache"))
    p = subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    return p.returncode, p.stdout, p.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_resolves_by_name(cell):
    spec = run.load_cell(cell)
    conf = {c["name"]: c for c in BENCHMARK["configs"]}[spec["cell"]
                                                        ["config"]]
    assert (ROOT / conf["file"]).is_file()
    assert (BENCH / "traffic" / f"{spec['cell']['traffic']}.json").is_file()
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
    assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.reader(m["name"]))
        assert m.get("moves", "setup_s") in {e["name"] for e in
                                             BENCHMARK["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_rehearsal_prints_the_contract_line(cell):
    rc, out, err = _run(ROOT, "--workload", cell, "--seed", "4294967301",
                        "--seconds", "2", "--trace", "0", "--rehearse")
    assert rc == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[:5] == list(KEYS) and list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    spec = run.load_cell(cell)
    assert set(line["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert line["device"]["platform"] == "cpu"


def test_traced_rehearsal_reports_per_layer_metrics():
    rc, out, err = _run(ROOT, "--workload", "vr2k-select", "--seed", "8",
                        "--seconds", "2", "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True
    layer = {m["name"] for m in run.load_cell("vr2k-select")["per_layer"]}
    # a CPU host has no device plane: the device metrics are left out,
    # never reported as 0
    assert set(line["metrics"]) <= layer
    assert "marshal_ms.sel" in line["metrics"]
    assert "device_idle_share.sel" not in line["metrics"]


def test_no_chip_no_result():
    rc, out, err = _run(ROOT, "--workload", "vr2k-select", "--seed", "1",
                        "--seconds", "2", "--trace", "0")
    assert rc != 0
    assert not out.strip() or not out.strip().splitlines()[-1].startswith(
        "{")


def test_new_cell_is_data_only(tmp_path):
    """A copy of the benchmark gains a configuration and a mix as new
    files and entries; no file that was there changes; the cell runs."""
    for name in ("bench", "src"):
        shutil.copytree(ROOT / name, tmp_path / name,
                        ignore=shutil.ignore_patterns(".jax_cache",
                                                      "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((BENCH / "configs" / "mot16-1080p-served.json")
                     .read_text())
    cfg["name"] = "mot16-crowd"
    (tmp_path / "bench/configs/mot16-crowd.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/crowd-uniform.json").write_text(json.dumps(
        {"arrivals": {"kind": "poisson", "rate_per_s": 5.0},
         "queries": {"kind": "ranges", "class": "sel",
                     "labels": {"person": 1.0}, "lengths": [8, 30]},
         "sample": 4}))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "mot16-crowd", "source": "x",
                             "file": "bench/configs/mot16-crowd.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "crowd", "config": "mot16-crowd",
                               "traffic": "crowd-uniform", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "sel" in m["name"] and "workloads" in m:
            m["workloads"].append("crowd")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    rc, out, err = _run(tmp_path, "--workload", "crowd", "--seed", "3",
                        "--seconds", "2", "--trace", "0", "--rehearse")
    assert rc == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert {"setup_s", "sel_p50_ms"} <= set(line["metrics"])
    for p, data in before.items():
        assert p.read_bytes() == data, p
