"""Per-layer metrics read from the program's own spans and counters.

A traced rehearsal of each decoding cell prints the span metrics with
numbers; a program without the recorder, or one that lost records of the
window, leaves them out; and the decode program still carries the name
that the trace reduction matches.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import spans  # noqa: E402

SPAN_METRICS = {
    "vr2k-select": ["queue_ms.sel", "batch_plans.sel", "gather_ms.sel",
                    "d2h_ms.sel", "scatter_ms.sel"],
    "vr2k-scan": ["gather_ms.scan", "d2h_ms.scan", "scatter_ms.scan",
                  "crop_ms.scan"],
}


@pytest.mark.parametrize("cell", sorted(SPAN_METRICS))
def test_traced_rehearsal_reports_program_spans(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(BENCH / ".jax_cache"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "2147483749", "--seconds", "2",
                        "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    for name in SPAN_METRICS[cell]:
        assert line["metrics"][name]["value"] > 0, name
    assert line["metrics"][SPAN_METRICS[cell][1]]["unit"] in ("ms", "plans")


def test_span_readers_report_nothing_without_the_recorder(monkeypatch):
    from repro.utils import trace

    ctx = types.SimpleNamespace(t0=time.monotonic(), seconds=5.0)
    with trace.span("tasm.decode.d2h"):
        pass
    assert spans.mean_ms(ctx, "tasm.decode.d2h") > 0
    # the parent commit's program has no recorder to import
    monkeypatch.setitem(sys.modules, "repro.utils.trace", None)
    monkeypatch.delattr("repro.utils.trace", raising=False)
    assert spans.named(ctx, "tasm.decode.d2h") is None
    assert run.reader("d2h_ms.scan")(ctx) is None


def test_span_readers_report_nothing_after_records_were_lost(monkeypatch):
    from repro.utils import trace

    small = trace.Recorder(maxlen=2)
    monkeypatch.setattr(trace, "window", small.window)
    ctx = types.SimpleNamespace(t0=time.monotonic(), seconds=5.0)
    for _ in range(3):
        small.count("tasm.batch_plans", 4)
    assert spans.named(ctx, "tasm.batch_plans") is None
    assert run.reader("batch_plans.sel")(ctx) is None


def test_decode_program_keeps_the_name_the_trace_reduction_matches():
    import jax
    import numpy as np

    from repro.kernels.decode import ops

    q = jax.ShapeDtypeStruct((2, 64, 8, 8), np.int16)
    lowered = ops._decode_fused.lower(q, qp=8, use_pallas=False,
                                      interpret=False)
    module = lowered.as_text().splitlines()[0]
    assert module.startswith("module @") and run.DECODE_PROGRAM in module
