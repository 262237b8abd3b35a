"""A run whose timed path is broken underneath reads ``correct: false``.

Each test drives a whole rehearsal run in this process (so the server, and
the fault planted in it, live here; the load generator is its own process)
and plants one fault a cell can have: an answer altered where it is
produced (the batched decode), half of the regions of each answer left
out, and the control -- the decode at ``Precision.HIGH``, one step below
the ``HIGHEST`` the configurations state, in the program's place -- which
the harness's own comparison, tie search included, has to fail.  In a
cluster cell the decode runs in node processes, so the fault is planted in
the first node through ``run.py --node-fault``.
"""
from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import run  # noqa: E402

CELLS = [w["name"] for w in json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]


def _cluster(cell: str) -> bool:
    return "cluster" in run.load_cell(cell)["config"]


def _line(capsys, monkeypatch, tmp_path, cell: str, *extra) -> dict:
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = run.run(run.parse_args(["--workload", cell, "--seed", "21",
                                 "--seconds", "2", "--trace", "0",
                                 "--rehearse", *extra]))
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_altered_decode_is_caught(cell, capsys, monkeypatch, tmp_path):
    import repro.codec.batch as batch

    real = batch.decode_tile_batch

    def altered(items, **kw):
        return [a + np.float32(0.01) for a in real(items, **kw)]

    if _cluster(cell):
        line = _line(capsys, monkeypatch, tmp_path, cell,
                     "--node-fault", "altered")
    else:
        monkeypatch.setattr(batch, "decode_tile_batch", altered)
        line = _line(capsys, monkeypatch, tmp_path, cell)
    assert line["correct"] is False
    c = line["checks"]["pixel_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_decode_is_caught(cell, capsys, monkeypatch,
                                         tmp_path):
    import repro.codec.batch as batch

    if _cluster(cell):
        line = _line(capsys, monkeypatch, tmp_path, cell,
                     "--node-fault", "high")
    else:
        monkeypatch.setattr(batch, "decode_fused_op",
                            control.decode_op("high"))
        line = _line(capsys, monkeypatch, tmp_path, cell)
    assert line["correct"] is False
    c = line["checks"]["pixel_gap"]
    assert c["value"] > c["limit"]
    assert line["checks"]["region_key_mismatches"]["value"] == 0


def test_half_the_regions_left_out_is_caught(capsys, monkeypatch, tmp_path):
    from repro.core.scheduler import ScanScheduler

    real = ScanScheduler._finish_one

    def halved(self, *a, **kw):
        res = real(self, *a, **kw)
        for v, regs in res.regions_by_video.items():
            del regs[1::2]
        return res

    monkeypatch.setattr(ScanScheduler, "_finish_one", halved)
    line = _line(capsys, monkeypatch, tmp_path, "vr2k-select")
    assert line["correct"] is False
    assert line["checks"]["region_key_mismatches"]["value"] > 0
