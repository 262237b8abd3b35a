"""Trace reduction: union of busy intervals, program time, idle gaps and
the roofline arithmetic, on hand-made traces and on one recorded on a TPU
v5e (``data/v5e_trace.json``, from ``run.py --trace 1 --keep-trace``)."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

DATA = BENCH / "tests" / "data" / "v5e_trace.json"


def _doc():
    # one device; ops [10,20] [15,30] [50,60] ns x 1000 inside window
    # [0, 100]; the decode program's kernel (a custom call) ran [10,20]
    us = 1000
    kernel = "%_decode_fused.1 = f32[32,8192,8,8]{3,2,1,0} custom-call(s16)"
    return {"window": [0, 100 * us],
            "device": {"/device:TPU:0": {
                "XLA Ops": [[kernel, 10 * us, 10 * us],
                            ["copy", 15 * us, 15 * us],
                            ["fusion", 50 * us, 10 * us]],
                "XLA Modules": [["jit__decode_fused(1)", 10 * us, 20 * us],
                                ["jit_other(2)", 50 * us, 5 * us],
                                ["jit__decode_fused(1)", 55 * us, 5 * us]]}},
            "host": [["t1", "gather", 31 * us, 18 * us],
                     ["t2", "short", 61 * us, 30 * us],
                     ["t3", "long", 60 * us, 40 * us]]}


def test_union_gaps_and_program_time():
    r = trace_reduce.reduce(_doc(), "_decode_fused")
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(30e-6)          # [10,30] + [50,60]
    assert r["kernel_s"] == pytest.approx(10e-6)
    assert r["top_ops"] == [["copy", pytest.approx(15e-6)],
                            ["_decode_fused.1 f32[32,8192,8,8]",
                             pytest.approx(10e-6)],
                            ["fusion", pytest.approx(10e-6)]]
    # gaps: [60,100] (40), [30,50] (20), [0,10] (10), longest first
    assert [g[1] for g in r["gaps"]] == pytest.approx([40e-6, 20e-6,
                                                       10e-6])
    assert r["gaps"][0][0] == "t3: long"
    assert r["gaps"][1][0] == "t1: gather"
    assert r["gaps"][2][0] == "no host event"


def test_no_device_plane_reads_nothing():
    assert trace_reduce.reduce({"window": None, "device": {}, "host": []},
                               "_decode_fused") is None


def test_op_names_are_shortened():
    raw = ("%copy.1 = f32[32,8192,8,8]{1,3,2,0:T(8,128)} copy(f32[32,8192,"
           "8,8]{3,2,1,0:T(8,128)} %_decode_fused.1)")
    assert trace_reduce.op_name(raw) == "copy.1 f32[32,8192,8,8]"
    assert trace_reduce.op_name("fusion") == "fusion"


def test_roofline_arithmetic_by_hand():
    # 1e6 pixels x 6 B = 6e6 B; at 819e9 B/s that is 7.326e-6 s; in 1e-3 s
    # of program time that is 0.7326%
    pct = trace_reduce.roofline_pct(1e6 * 6, 1e-3, "TPU v5 lite")
    assert pct == pytest.approx(100 * 6e6 / 819e9 / 1e-3)
    assert pct == pytest.approx(0.73260073, rel=1e-6)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        trace_reduce.roofline_pct(1.0, 1.0, "TPU v99")
    with pytest.raises(KeyError):
        trace_reduce.peak("cpu", "hbm_bytes_per_s")


def test_recorded_v5e_trace():
    """A trace of a short ``vr2k-select`` window on one v5e chip."""
    doc = json.loads(DATA.read_text())
    assert DATA.stat().st_size < 1 << 20
    r = trace_reduce.reduce(doc, "_decode_fused")
    assert r is not None
    lo, hi = doc["window"]
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    # busy: recompute the union by a plain sweep over nanosecond edges
    ops = [e for p in doc["device"].values() for e in p.get("XLA Ops", [])]
    edges = sorted({max(lo, min(hi, x)) for _, s, d in ops
                    for x in (s, s + d)} | {lo, hi})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= s + d for _, s, d in ops))
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    # its kernel is the custom call, without the relayout copies around it
    kernel = sum(max(0, min(s + d, hi) - max(s, lo)) for n, s, d in ops
                 if " custom-call(" in n and n.startswith("%_decode_fused"))
    assert r["kernel_s"] == pytest.approx(kernel / 1e9)
    assert 0.5 * r["busy_s"] < r["kernel_s"] < r["busy_s"]
    assert all(" f32[" in n or " s16[" in n or " (" in n
               for n, _ in r["top_ops"])
    assert len(r["gaps"]) <= 10 and len(r["top_ops"]) <= 10
